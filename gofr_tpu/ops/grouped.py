"""Grouped matmul over expert stacks: rows sorted by expert, one weight each.

The routed FFN (models.moe.routed_ffn) lays its (token, expert) pairs out
sorted by expert, every expert's rows starting on a tile boundary, and calls
`grouped_matmul` three times (gate, up, down). Row tile t multiplies the
weight of expert `tile_expert[t]`:

- Pallas TPU kernel (`moe_grouped_matmul` in a device trace): grid (column
  tiles, row tiles), the tile -> expert map a scalar-prefetch operand that
  the weight's BlockSpec reads, so an expert nobody was routed to is never
  fetched and consecutive tiles of one expert reuse the block already in
  VMEM. An int8 stack streams at one byte a weight and is widened tile by
  tile in VMEM (no dequantised copy of the stack in HBM); its per-column
  scale multiplies the f32 accumulator.
- `jax.lax.ragged_dot` over the same layout (group sizes = the padded
  counts): the off-TPU path and the kernel's oracle. It needs the stack in
  the activations' dtype, so an int8 stack is widened whole.

A stack comes as `LayerOf(stack [L, E, in, out], layer)`: every layer's
experts left whole and which layer this is. The kernel reads its blocks at
(layer, expert) through one more scalar-prefetch operand; handing it the
layer's own [E, in, out] slice instead makes XLA copy 200 MB of int8 out of
the stack before every call (a custom call's operand is a whole array), which
was 58% of the device's time on the v5e before this form (PERF.md, PR 29). A
caller that holds one layer's stack alone wraps it as a stack of one
(`LayerOf.of`).
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


class LayerOf(NamedTuple):
    """One layer of a stack that is left whole (models.transformer._layer_scan
    hands the expert stacks to a layer this way)."""

    stack: Any  # [L, ...] array, or a QTensor of such
    layer: Any  # scalar int32

    @classmethod
    def of(cls, w) -> "LayerOf":
        """`w` as it is, or one layer's own stack [E, ...] as a stack of one."""
        return w if isinstance(w, cls) else cls(jax.tree.map(lambda a: a[None], w), 0)

    def sliced(self):
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, self.layer, 0, keepdims=False), self.stack
        )


def grouped_kernel_why_not(k: int, n: int, tile_rows: int, *, interpret: bool = False) -> str:
    """Why the Pallas grouped matmul cannot serve these shapes, or ""."""
    if not interpret and jax.default_backend() != "tpu":
        return f"backend {jax.default_backend()} is not tpu"
    if k % 128 or n % 128:
        return f"expert matrix [{k}, {n}] is not whole 128-lane tiles"
    if tile_rows % 16:
        return f"row tile {tile_rows} is not a multiple of 16"
    return ""


def _column_tile(n: int, most: int = 1024) -> int:
    """The widest multiple of 128 that divides n, up to `most`."""
    return max(t for t in range(128, min(n, most) + 1, 128) if n % t == 0)


def _gmm_kernel(te_ref, used_ref, layer_ref, x_ref, w_ref, *rest, scaled: bool):
    if scaled:
        s_ref, o_ref = rest
    else:
        (o_ref,) = rest
    i = pl.program_id(1)

    @pl.when(i < used_ref[0])
    def _live():
        x = x_ref[...]
        acc = jnp.dot(x, w_ref[...].astype(x.dtype), preferred_element_type=jnp.float32)
        if scaled:
            acc = acc * s_ref[...].astype(jnp.float32)
        o_ref[...] = acc.astype(o_ref.dtype)

    @pl.when(i >= used_ref[0])
    def _spare():  # tiles past the last routed row: nothing to multiply
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("tile_rows", "interpret"))
def _gmm_call(x, w, s, tile_expert, used_tiles, layer, *, tile_rows: int, interpret: bool):
    """x [rows, k] against w [L, E, k, n] at `layer` (s [L, E, 1, n] or None)."""
    rows, k = x.shape
    n = w.shape[-1]
    tn = _column_tile(n)
    scaled = s is not None

    def at(j, i, te, used, layer):
        return (layer[0], te[i], 0, j)

    in_specs = [
        pl.BlockSpec((tile_rows, k), lambda j, i, te, used, layer: (i, 0)),
        pl.BlockSpec((None, None, k, tn), at),
    ]
    operands = [x, w]
    if scaled:
        in_specs.append(pl.BlockSpec((None, None, 1, tn), at))
        operands.append(s)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, scaled=scaled),
        name="moe_grouped_matmul",  # what a device trace calls the kernel
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, rows // tile_rows),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((tile_rows, tn), lambda j, i, te, used, layer: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=48 * 1024 * 1024),
        interpret=interpret,
    )(
        tile_expert.astype(jnp.int32), used_tiles.astype(jnp.int32).reshape(1),
        jnp.asarray(layer, jnp.int32).reshape(1), *operands,
    )


def grouped_matmul(
    x: jnp.ndarray,  # [rows, k] sorted by expert, each expert's rows from a tile boundary
    w: LayerOf,  # stacks [L, E, k, n], an array or a QTensor (q int8, s [L, E, 1, n]), and the layer
    tile_expert: jnp.ndarray,  # [rows / tile_rows] int32: the expert of each row tile
    used_tiles: jnp.ndarray,  # scalar int32: tiles that hold routed rows
    padded_counts: jnp.ndarray,  # [E] int32: rows laid out per expert (multiples of tile_rows)
    *,
    tile_rows: int,
    use_kernel: bool | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """-> [rows, n] in x's dtype; rows of spare tiles come back zero."""
    quant = hasattr(w.stack, "q")  # a models.quant.QTensor, without importing the models here
    wq = w.stack.q if quant else w.stack
    if use_kernel is None:
        use_kernel = not grouped_kernel_why_not(
            wq.shape[-2], wq.shape[-1], tile_rows, interpret=interpret
        )
    if use_kernel:
        return _gmm_call(
            x, wq, w.stack.s if quant else None, tile_expert, used_tiles, w.layer,
            tile_rows=tile_rows, interpret=interpret,
        )
    own = w.sliced()
    out = jax.lax.ragged_dot(
        x, (own.q if quant else own).astype(x.dtype), padded_counts.astype(jnp.int32)
    )
    if quant:
        # a row's scale is its expert's: spread the tile -> expert map over rows
        row_expert = jnp.repeat(tile_expert, tile_rows, total_repeat_length=x.shape[0])
        out = out * own.s.astype(x.dtype)[row_expert, 0]
    return out

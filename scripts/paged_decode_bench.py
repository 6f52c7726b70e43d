#!/usr/bin/env python
"""Microbenchmark of the paged-decode kernels alone, on the chip.

    python scripts/paged_decode_bench.py [gqa] [latent]        # on a TPU host
    PYTHONPATH=<another checkout> python scripts/paged_decode_bench.py latent

Times layer-calls of ``ops.attention`` over a pool stored as the engine
stores it, the whole stack ``[L, NB, B, W]`` read at a layer index:

- ``gqa``: the GQA kernel at the shapes of the cells that run it, 16 lanes
  each: ``qwen2-7b.reason-closed`` (hq 28, hkv 4, a table of 120, 2,394
  blocks, 28 layers), ``k-exaone-236b-a23b.mixed-closed``'s full layers
  (64 / 8 heads, a table of 520, 8,208 blocks, 3 layers) and window layers
  (the same heads over a band of 128 keys, 240 blocks, 10 layers), and
  ``olmo-hybrid-7b.chat-closed``'s full layers (30 / 30 heads, a table of
  129, 2,080 blocks, 8 layers); head_dim 128, 16-token blocks, bf16 pools.
  The kernel's partials, the same without its dots, the whole
  paged_chunk_decode_attention around it and, at the cell's mean context,
  the dense-gather path (use_kernel=False);
- ``latent``: ``glm-4.7-flash.think-closed``'s (20 heads over rows of
  512 | 128, 16-token blocks, a table of 200, pools of 5,104 blocks and 13
  layers), 16 lanes; the latent kernel's partials and
  mla_paged_chunk_decode_attention around it.

Tables are in the pool's own allocation order: the lanes take blocks from
one free list as they grow, in turn. Every layer of the stack is called in
one jit, four times over, so the host's dispatch is a small share of the
number; a row gives the least and the median of 7 timed runs per call, the
share of the HBM roofline the least reaches over the bytes the bands' rows
count, and max |kernel - gather| on the same inputs. "Without its dots"
(``nodots_us_*``) traces the kernel with ``jax.lax.dot_general`` replaced
by a stand-in of the same shape (``without_dots``): what is left is the
copies, the waits and the per-step work around the matmuls. ``PYTHONPATH`` picks
the checkout whose kernels are timed (this one's by default); each row
names it. One JSON line per row on stdout.
"""

from __future__ import annotations

import contextlib
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.append(REPO)  # after PYTHONPATH: another checkout named there wins

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from gofr_tpu.ops import attention as A  # noqa: E402

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(A.__file__))))
BLOCK, STEPS, REPS, D = 16, 8, 4, 128
# the GQA kernel's cells: heads, kv heads, table width, pool blocks, layers,
# window (the band a lane reads), 16 lanes' contexts (the cell's mean first)
GQA_SHAPES = {
    "qwen2": (28, 4, 120, 2394, 28, 0, {"700": 700, "16": 16, "1792": 1792 - STEPS, "spread 300-1100": (300, 1100)}),
    "k-exaone full": (64, 8, 520, 8208, 3, 0, {"1280": 1280, "16": 16, "spread 64-4072": (64, 4072)}),
    "k-exaone window": (64, 8, 520, 240, 10, 128, {"1280": 1280, "16": 16, "spread 64-4072": (64, 4072)}),
    "olmo": (30, 30, 129, 2080, 8, 0, {"800": 800, "16": 16, "spread 64-2040": (64, 2040)}),
}
# glm-4.7-flash: heads, latent, rope stored / counted, table width, pool blocks, layers
MHQ, C, R, ROPE, MLA_TBL, MLA_POOL, MLA_LAYERS = 20, 512, 128, 64, 200, 5104, 13
MLA_SCALE = (192 + ROPE) ** -0.5


def allocation_order(contexts: np.ndarray, n_tbl: int, n_pool: int, starts=None) -> np.ndarray:
    """[lanes, n_tbl] tables as one free list hands blocks out to lanes that
    grow in turn (a lane takes its next block as it crosses a block's end),
    each lane started 7 blocks after the one before; the slots outside a
    lane's band [starts, contexts) name the pool's last block, which no
    lane holds (a window's reclaimed blocks)."""
    lanes = len(contexts)
    need = -(-np.asarray(contexts) // BLOCK)
    first = np.zeros(lanes, int) if starts is None else np.asarray(starts) // BLOCK
    order = sorted((j + 7 * i, i, j) for i in range(lanes) for j in range(first[i], need[i]))
    assert len(order) < n_pool
    tables = np.full((lanes, n_tbl), n_pool - 1, np.int32)
    for block, (_, i, j) in enumerate(order):
        tables[i, j] = block
    return tables


def per_call_us(fn, args, layers: int) -> tuple[float, float]:
    """(least, median) microseconds per layer-call: every layer of the stack
    REPS times inside one jit, 7 timed runs after one that compiles. `fn`
    takes (layer, q, *rest) and `args` is (qs [layers * REPS, ...], *rest)."""

    def calls(qs, *rest):
        idx = jnp.arange(layers * REPS, dtype=jnp.int32) % layers
        return jax.lax.map(lambda lq: fn(lq[0], lq[1], *rest), (idx, qs))

    run = jax.jit(calls)
    jax.block_until_ready(run(*args))
    times = []
    for _ in range(7):
        t0 = time.perf_counter()
        jax.block_until_ready(run(*args))
        times.append((time.perf_counter() - t0) / (layers * REPS) * 1e6)
    return min(times), statistics.median(times)


def max_abs_diff(attend, args, layers: int) -> float:
    """|kernel - gather| at the last layer with the first query."""
    qs, *rest = args
    one = (jnp.int32(layers - 1), qs[0], *rest)
    got = jax.jit(attend(True))(*one)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(attend(False))(*one)
    return float(jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))))


@contextlib.contextmanager
def without_dots():
    """jax.lax.dot_general, as a kernel's body looks it up while it is
    traced, replaced by a stand-in of the dot's shape: the first column of
    the (2-D, rows-first) left operand broadcast along the lanes. What only
    fed a dot's right operand (the page rows, widened and masked) goes with
    it; the copies, the waits and the softmax's own work stay. The jit caches
    are cleared on both sides, so no program traced one way is run the other."""
    real = jax.lax.dot_general

    def stand_in(a, b, dimension_numbers, **_):
        (_, cb), _ = dimension_numbers
        cols = [n for i, n in enumerate(b.shape) if i not in cb]
        return jnp.broadcast_to(a[:, :1].astype(jnp.float32), (a.shape[0], *cols))

    jax.clear_caches()
    jax.lax.dot_general = stand_in
    try:
        yield
    finally:
        jax.lax.dot_general = real
        jax.clear_caches()


def _rand(key, shape):
    return jax.random.normal(key, shape, jnp.float32).astype(jnp.bfloat16)


# -- GQA (qwen2, k-exaone, olmo) ----------------------------------------------------


def gqa_inputs(hq, hkv, n_pool, layers, lanes=16):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    return dict(
        qs=_rand(ks[0], (layers * REPS, lanes, 1, hq, D)),
        k_pool=_rand(ks[1], (layers, n_pool, BLOCK, hkv * D)),
        v_pool=_rand(ks[2], (layers, n_pool, BLOCK, hkv * D)),
        k_buf=_rand(ks[3], (lanes, STEPS, hkv, D)), v_buf=_rand(ks[4], (lanes, STEPS, hkv, D)),
    )


def band_start(ctx, window: int):
    """The band's first position, as paged_chunk_decode_attention sets it at
    the step the calls take (3)."""
    return np.maximum(np.asarray(ctx) + 3 - window + 1, 0) if window else np.zeros_like(ctx)


def gqa_args(x, ctx, n_tbl, n_pool, window):
    tables = jnp.asarray(allocation_order(ctx, n_tbl, n_pool, band_start(ctx, window)))
    return (x["qs"], x["k_pool"], x["v_pool"], tables, x["k_buf"], x["v_buf"],
            jnp.asarray(ctx, jnp.int32), jnp.asarray(3, jnp.int32))


def gqa_attend(window):
    def attend(use_kernel):
        def fn(layer, q, k_pool, v_pool, tables, k_buf, v_buf, lengths, step):
            return A.paged_chunk_decode_attention(
                q, k_pool, v_pool, tables, k_buf, v_buf, lengths, step,
                layer=layer, window=window, use_kernel=use_kernel,
            )
        return fn
    return attend


def gqa_partials(window):
    def fn(layer, q, k_pool, v_pool, tables, k_buf, v_buf, lengths, step):
        lo = jnp.maximum(lengths + step - window + 1, 0) if window else jnp.zeros_like(lengths)
        return A._paged_decode_partials(q[:, 0], k_pool, v_pool, tables, lo, lengths, layer, scale=D ** -0.5)
    return fn


def gqa_rows(rng, peak: float):
    for shape, (hq, hkv, n_tbl, n_pool, layers, window, contexts) in GQA_SHAPES.items():
        x = gqa_inputs(hq, hkv, n_pool, layers)
        for i, (name, ctx) in enumerate(contexts.items()):
            ctx = rng.integers(*ctx, 16) if isinstance(ctx, tuple) else np.full(16, ctx)
            args = gqa_args(x, ctx, n_tbl, n_pool, window)
            band = ctx - band_start(ctx, window)
            row = timed(f"gqa {shape}", 16, name, int(band.sum()) * hkv * D * 2 * 2, peak, layers,
                        gqa_partials(window), gqa_attend(window), args)
            if i == 0:  # the cell's mean context
                g_min, g_med = per_call_us(gqa_attend(window)(False), args, layers)
                row["gather_us_min"], row["gather_us_median"] = round(g_min, 2), round(g_med, 2)
            yield row
        del x


# -- latent (glm) -------------------------------------------------------------------


def latent_inputs(lanes: int = 16):
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    return dict(
        qs=_rand(ks[0], (MLA_LAYERS * REPS, lanes, 1, MHQ, C + R)).at[..., C + ROPE:].set(0),
        c_pool=_rand(ks[1], (MLA_LAYERS, MLA_POOL, BLOCK, C)),
        r_pool=_rand(ks[2], (MLA_LAYERS, MLA_POOL, BLOCK, R)).at[..., ROPE:].set(0),
        c_buf=_rand(ks[3], (lanes, STEPS, 1, C)),
        r_buf=_rand(ks[4], (lanes, STEPS, 1, R)).at[..., ROPE:].set(0),
    )


def latent_cases(rng, lanes: int = 16) -> dict:
    return {
        "16": np.full(lanes, 16), "1160": np.full(lanes, 1160), "3064": np.full(lanes, 3072 - STEPS),
        "spread 128-3064": rng.integers(128, 3072 - STEPS, lanes),
    }


def latent_args(x, ctx):
    tables = jnp.asarray(allocation_order(ctx, MLA_TBL, MLA_POOL))
    return (x["qs"], x["c_pool"], x["r_pool"], tables, x["c_buf"], x["r_buf"],
            jnp.asarray(ctx, jnp.int32), jnp.asarray(3, jnp.int32))


def latent_attend(use_kernel):
    def fn(layer, q, c_pool, r_pool, tables, c_buf, r_buf, lengths, step):
        return A.mla_paged_chunk_decode_attention(
            q, c_pool, r_pool, tables, c_buf, r_buf, lengths, step,
            scale=MLA_SCALE, layer=layer, use_kernel=use_kernel,
        )
    return fn


def latent_partials(layer, q, c_pool, r_pool, tables, c_buf, r_buf, lengths, step):
    return A._mla_paged_decode_call(
        q[:, 0], c_pool, r_pool, tables, jnp.zeros_like(lengths), lengths, layer,
        scale=MLA_SCALE, interpret=False,
    )


def latent_bytes(ctx) -> int:
    """What latent_decode_roofline_pct counts: 512 + 64 bf16 a row."""
    return int(np.sum(ctx)) * (C + ROPE) * 2


def latent_rows(rng, peak: float):
    x = latent_inputs()
    for name, ctx in latent_cases(rng).items():
        yield timed("latent", 16, name, latent_bytes(ctx), peak, MLA_LAYERS,
                    latent_partials, latent_attend, latent_args(x, ctx))


def timed(kind, lanes, contexts, kv_bytes, peak, layers, partials, attend, args) -> dict:
    kern_min, kern_med = per_call_us(partials, args, layers)
    with without_dots():
        bare_min, bare_med = per_call_us(partials, args, layers)
    att_min, att_med = per_call_us(attend(True), args, layers)
    return {
        "checkout": CHECKOUT, "device": jax.devices()[0].device_kind, "kind": kind, "lanes": lanes,
        "contexts": contexts, "kernel_us_min": round(kern_min, 2), "kernel_us_median": round(kern_med, 2),
        "nodots_us_min": round(bare_min, 2), "nodots_us_median": round(bare_med, 2),
        "attend_us_min": round(att_min, 2), "attend_us_median": round(att_med, 2),
        "kv_mb": round(kv_bytes / 1e6, 3),
        "kernel_roofline_pct": round(100 * kv_bytes / peak / (kern_min * 1e-6), 2),
        "max_abs_diff": max_abs_diff(attend, args, layers),
    }


def hbm_peak() -> float:
    """The chip's HBM bytes/s from the benchmark's peaks table; no default."""
    dev = jax.devices()[0]
    with open(os.path.join(REPO, "benchmarks", "peaks.json")) as f:
        return json.load(f)[dev.device_kind]["hbm_bytes_per_s"]


def main(argv) -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU here ({dev.platform}): a time from this device is not a result", file=sys.stderr)
        return 2
    peak = hbm_peak()
    rng = np.random.default_rng(0)
    for kind in argv or ["gqa", "latent"]:
        for row in {"gqa": gqa_rows, "latent": latent_rows}[kind](rng, peak):
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

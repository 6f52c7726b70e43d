"""Pipeline parallelism: the transformer layer stack sharded by DEPTH over
a mesh axis, microbatches streamed through the stages with activations
hopping stage-to-stage via ppermute.

SURVEY.md §2.8 lists PP as the one optional ("stretch") parallelism row —
the reference is a single-process web framework with no ML execution, so
there is no reference analogue; this is the TPU-native design:

- **Stage = contiguous slice of layers.** Params keep their stacked
  [n_layers, ...] leaves; sharding them P("stage") over the leading axis
  gives each device an [L/S, ...] slice with NO reshapes or per-stage
  param pytrees — the same `lax.scan` layer body as single-device runs
  over the local slice.
- **GPipe schedule inside one `lax.scan`.** T = n_micro + S - 1 ticks;
  at tick t stage 0 injects microbatch t, every stage applies its slice,
  and outputs rotate (i -> i+1) via `lax.ppermute`. All devices run the
  identical program (SPMD) — stage identity is `lax.axis_index`, so the
  schedule compiles to one executable with a collective-permute per tick,
  which XLA overlaps with the next tick's compute on ICI.
- **Autodiff-native.** No hand-written backward: jax transposes the scan
  (reverse-time) and each ppermute (inverse permutation), yielding the
  standard reverse pipeline schedule. `jax.checkpoint` around the stage
  body bounds activation memory to O(local_layers) per microbatch.
- **Bubble** = (S-1)/(n_micro+S-1) idle fraction per pass (GPipe); pick
  n_micro >= 4*S to keep it under ~20%. PP pays off when a model's
  weights + optimizer state exceed one chip's HBM and TP's per-layer
  collectives would cross slow links — stages only ever send one
  activation tensor per tick point-to-point over the ring.

Composes with data parallelism: a ("data", "stage") mesh shards the
microbatch dim over "data" outside shard_map (GSPMD inserts the gradient
psum) while this module owns "stage" inside shard_map.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import optax
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.transformer import TransformerConfig, _embed_tokens, _layer_body, _unembed
from ..ops import rms_norm

__all__ = ["pipeline_layers", "pp_lm_loss", "make_pp_train_step", "pp_param_shardings"]


def _stage_forward(cfg: TransformerConfig, layers_local, x, positions):
    """Run this stage's local layer slice (leaves [L/S, ...]) over x."""

    @jax.checkpoint
    def body(x, lp):
        x, _, _ = _layer_body(
            cfg, x, lp, positions,
            k_cache=None, v_cache=None, cache_length=None, decode=False,
        )
        return x, None

    x, _ = lax.scan(body, x, layers_local)
    return x


def pipeline_layers(
    cfg: TransformerConfig,
    mesh: Mesh,
    axis: str = "stage",
) -> Callable:
    """Returns pp_fn(layers_params, x_mb) -> y_mb.

    layers_params: the model's ["layers"] subtree, leaves [L, ...] sharded
    P(axis) on the leading (layer) axis; L must divide by mesh.shape[axis].
    x_mb: [n_micro, mb, s, d] embedded activations, replicated over axis.
    Returns [n_micro, mb, s, d] last-stage outputs, replicated.
    """
    S = mesh.shape[axis]
    perm = [(i, (i + 1) % S) for i in range(S)]

    def pp_body(layers_local, x_mb):
        idx = lax.axis_index(axis)
        M = x_mb.shape[0]
        T = M + S - 1
        b, s = x_mb.shape[1], x_mb.shape[2]
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        # mark the carries device-varying up front (each stage's state and
        # output buffer genuinely differ) — jax 0.9's vma tracking rejects
        # a scan whose carry starts replicated and becomes varying
        state = lax.pcast(jnp.zeros(x_mb.shape[1:], x_mb.dtype), (axis,), to="varying")
        out = lax.pcast(jnp.zeros_like(x_mb), (axis,), to="varying")

        def tick(carry, t):
            state, out = carry
            # stage 0 injects microbatch t (clipped read; drain ticks
            # t >= M re-feed mb M-1, whose recomputed output lands outside
            # the keep window and is discarded)
            inj = lax.dynamic_index_in_dim(
                x_mb, jnp.clip(t, 0, M - 1), 0, keepdims=False
            )
            x_in = jnp.where(idx == 0, inj, state)
            y = _stage_forward(cfg, layers_local, x_in, positions)
            # last stage stores tick t's result as microbatch t-(S-1)
            m = t - (S - 1)
            mc = jnp.clip(m, 0, M - 1)
            cur = lax.dynamic_index_in_dim(out, mc, 0, keepdims=False)
            keep = (idx == S - 1) & (m >= 0) & (m < M)
            out = lax.dynamic_update_index_in_dim(
                out, jnp.where(keep, y, cur), mc, 0
            )
            state = lax.ppermute(y, axis, perm)
            return (state, out), None

        (state, out), _ = lax.scan(tick, (state, out), jnp.arange(T))
        # replicate the last stage's collected outputs to every stage
        out = lax.psum(jnp.where(idx == S - 1, out, jnp.zeros_like(out)), axis)
        return out

    return shard_map(
        pp_body, mesh=mesh, in_specs=(P(axis), P()), out_specs=P(),
    )


def pp_lm_loss(
    params: dict,
    cfg: TransformerConfig,
    tokens: jnp.ndarray,  # [b, s]
    mask: jnp.ndarray,  # [b, s] True = real token
    pp_fn: Callable,
    n_micro: int,
) -> jnp.ndarray:
    """Causal-LM cross entropy with the layer stack run through pp_fn.
    Embed/final-norm/unembed stay outside the pipeline (replicated): they
    are a single gather + one matmul, not worth a stage."""
    b, s = tokens.shape
    if b % n_micro != 0:
        raise ValueError(f"batch {b} not divisible by n_micro={n_micro}")
    x = _embed_tokens(params, cfg, tokens)
    x_mb = x.reshape(n_micro, b // n_micro, s, cfg.d_model)
    y = pp_fn(params["layers"], x_mb).reshape(b, s, cfg.d_model)
    y = rms_norm(y, params["final_norm"], cfg.norm_eps)
    logits = _unembed(params, cfg, y)
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    w = mask[:, 1:].astype(jnp.float32)
    return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)


def pp_param_shardings(
    cfg: TransformerConfig, mesh: Mesh, axis: str = "stage",
    untied: bool = False,
):
    """NamedSharding pytree: layer leaves stage-sharded on the leading
    (layer) axis, embed/final_norm (and unembed, if untied) replicated."""
    staged = NamedSharding(mesh, P(axis))
    repl = NamedSharding(mesh, P())
    extra = {"unembed": repl} if untied else {}
    layer_keys = [
        "attn_norm", "wq", "wkv", "wo", "mlp_norm",
        "w_gate", "w_up", "w_down",
    ]
    if getattr(cfg, "qkv_bias", False):  # Qwen2: biases are layer leaves too
        layer_keys += ["bq", "bkv"]
    return {
        **extra,
        "embed": repl,
        "final_norm": repl,
        "layers": {k: staged for k in layer_keys},
    }


def make_pp_train_step(
    cfg: TransformerConfig,
    mesh: Mesh,
    *,
    n_micro: int,
    axis: str = "stage",
    optimizer: optax.GradientTransformation | None = None,
    learning_rate: float = 3e-4,
) -> tuple[Callable, Callable, Callable]:
    """Pipeline-parallel analogue of parallel.train.make_train_step:
    returns (shard_fn, init_opt_fn, step_fn). n_layers must divide by
    mesh.shape[axis]; batch by n_micro."""
    if cfg.n_layers % mesh.shape[axis] != 0:
        raise ValueError(
            f"n_layers={cfg.n_layers} not divisible by {axis}={mesh.shape[axis]}"
        )
    opt = optimizer or optax.adamw(learning_rate)
    pp_fn = pipeline_layers(cfg, mesh, axis)

    def shard_fn(params):
        shardings = pp_param_shardings(
            cfg, mesh, axis, untied="unembed" in params
        )
        return jax.device_put(params, shardings)

    def init_opt_fn(params):
        return opt.init(params)

    def step_fn(params, opt_state, tokens, mask):
        loss, grads = jax.value_and_grad(pp_lm_loss)(
            params, cfg, tokens, mask, pp_fn, n_micro
        )
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    # same compile-observatory wrapping as parallel.train.make_train_step
    from ..profiling import instrument_jit

    return (
        shard_fn,
        instrument_jit("parallel.pp_init_opt", init_opt_fn, model="pipeline"),
        instrument_jit("parallel.pp_train_step", step_fn, model="pipeline"),
    )

"""Int8 weight quantization for serving.

Decode is HBM-bandwidth-bound: every step streams all weights once, so
int8 halves the floor (bf16 5.0 GB -> 2.5 GB for Gemma-2B). Symmetric
per-output-channel quantization: q int8 [in, out], scale bf16 [out];
activations stay bf16 and XLA fuses the int8->bf16 convert into the dot's
operand stream (no materialized dequantized copy).

QTensor is a pytree node, so quantized params flow through jit/scan/
device_put/shardings exactly like plain arrays — the layer stack scans over
stacked (q, s) leaves with zero code changes outside the matmul helper.

The embedding quantizes per-d-column so ONE scale vector serves both uses:
  gather:  emb.q[tokens] * s        (row lookup, scale on d)
  unembed: (x * s) @ emb.q.T        (scale folds into the activations)
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

__all__ = [
    "QTensor",
    "quantize",
    "qmm",
    "qmm_a8",
    "quantize_params",
    "quantize_param_specs",
    "init_params_quantized",
    "is_quantized",
]


class QTensor(NamedTuple):
    q: jnp.ndarray  # int8
    s: jnp.ndarray  # bf16 scale, broadcastable over the LAST axis

    @property
    def shape(self):
        return self.q.shape

    @property
    def dtype(self):  # reported dtype = compute dtype after dequant
        return self.s.dtype


def quantize(w: jnp.ndarray, dtype=jnp.bfloat16) -> QTensor:
    """Symmetric per-last-axis-channel int8.

    The amax reduction runs over axis=-2 ONLY (the contraction axis of the
    matmul), so stacked [L, in, out] weights get independent [L, 1, out]
    scales — one scale per (layer, output channel), and the scale leaf keeps
    the leading L axis so the layer-stack lax.scan slices it correctly."""
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=-2, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    return QTensor(q=q, s=scale.astype(dtype))


def qmm(x: jnp.ndarray, w) -> jnp.ndarray:
    """x @ w for plain arrays or QTensors (dequant fused into the dot).
    w.s has keepdims shape [1, ..., out]; broadcasting applies it to the
    dot's trailing output axis."""
    if isinstance(w, QTensor):
        return (x @ w.q.astype(x.dtype)) * w.s.astype(x.dtype)
    return x @ w


def qmm_a8(x: jnp.ndarray, w) -> jnp.ndarray:
    """x @ w with per-row dynamic activation quantization (W8A8).

    Prefill is MXU-compute-bound, and on v5e the convert(int8)->bf16 dot
    (qmm) is SLOWER than plain bf16 (measured 189 vs 233 TF/s — the convert
    doesn't ride the MXU), while native s8 x s8 -> s32 hits 294 TF/s. So
    the prefill path quantizes activations on the fly (symmetric per-row,
    like the weights' per-channel scheme) and issues an integer dot; the
    two scale vectors fold into the f32 accumulator output. Decode keeps
    qmm: it is HBM-bound and its activations are a single token row."""
    if not isinstance(w, QTensor):
        return x @ w
    import jax

    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True).astype(jnp.float32)
    sc = jnp.maximum(amax / 127.0, 1e-8)
    xq = jnp.clip(jnp.round(x.astype(jnp.float32) / sc), -127, 127).astype(jnp.int8)
    acc = jax.lax.dot_general(
        xq, w.q,
        (((x.ndim - 1,), (w.q.ndim - 2,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    out = acc.astype(jnp.float32) * sc * w.s.astype(jnp.float32).reshape(
        (1,) * (acc.ndim - 1) + (-1,)
    )
    return out.astype(x.dtype)


def is_quantized(params: dict) -> bool:
    return isinstance(params.get("embed"), QTensor)


_QUANT_KEYS = (
    "wq", "wkv", "wo", "w_gate", "w_up", "w_down",
    # latent attention's projections and the shared experts (the router and
    # its correction bias stay as they are: routing is decided in float32)
    "wq_a", "wq_b", "wkv_a", "wkv_b", "ws_gate", "ws_up", "ws_down",
    # a linear-attention layer's projections (models.hybrid; its conv, decay
    # and norm leaves stay float)
    "wk", "wv", "wz", "wb", "wa",
    # a lightning indexer's projections (its LayerNorm stays float)
    "wq_idx", "wk_idx", "w_idx",
)


def _map_groups(fn, layers):
    """fn over params["layers"]: one stacked dict, or a tuple of them
    (models.transformer.layer_groups)."""
    if isinstance(layers, (tuple, list)):
        return tuple(fn(g) for g in layers)
    return fn(layers)


def quantize_params(params: dict, dtype=jnp.bfloat16) -> dict:
    """Quantize the big matmul weights (+ embedding); norms stay bf16.
    Layer-stacked weights [L, in, out] get per-(L, out) scales."""
    if is_quantized(params):
        return params
    layers = _map_groups(
        lambda g: {k: (quantize(v, dtype) if k in _QUANT_KEYS else v) for k, v in g.items()},
        params["layers"],
    )
    out = {
        "embed": quantize(params["embed"], dtype),
        "final_norm": params["final_norm"],
        "layers": layers,
    }
    if "unembed" in params:  # untied lm_head (Llama): same [vocab, d] layout
        out["unembed"] = quantize(params["unembed"], dtype)
    return out


def init_params_quantized(rng, cfg, dtype=jnp.bfloat16, *, untied: bool = False) -> dict:
    """Random-weight int8 param tree built DIRECTLY on device.

    Benchmark/test initializer for models whose bf16 tree does not fit
    HBM: Gemma-7B is ~16.4 GB bf16 — over a v5e chip's 16 GB — but
    8.2 GB int8, so init-then-quantize would OOM before quantize ran.
    Draws int8 weights uniform in [-127, 127] with per-channel scales
    matching init_params' 1/sqrt(fan_in) magnitude; norms stay zeros
    (the real-weights path is models.checkpoint + quantize_params).
    ``untied`` adds the separate [vocab, d] output head the Llama, Mistral
    and Qwen2 families publish (untied-ness lives in the pytree)."""
    import jax

    d, hd, hq, hkv, ff, L = (
        cfg.d_model, cfg.head_dim, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff, cfg.n_layers,
    )
    keys = iter(jax.random.split(rng, 8))

    def qw(shape, fan_in):
        q = jax.random.randint(next(keys), shape, -127, 128, jnp.int8)
        # scale so dequantized std ~ 1/sqrt(fan_in) (uniform int8 std ~73)
        s_shape = shape[:-2] + (1, shape[-1])
        s = jnp.full(s_shape, 1.0 / (73.0 * fan_in**0.5), dtype)
        return QTensor(q=q, s=s)

    bias = (
        {
            "bq": jnp.zeros((L, hq * hd), dtype),
            "bkv": jnp.zeros((L, 2 * hkv * hd), dtype),
        }
        if getattr(cfg, "qkv_bias", False)
        else {}
    )
    tree = {
        "embed": qw((cfg.vocab_size, d), d),
        "final_norm": jnp.zeros((d,), dtype),
        "layers": {
            **bias,
            "attn_norm": jnp.zeros((L, d), dtype),
            "wq": qw((L, d, hq * hd), d),
            "wkv": qw((L, d, 2 * hkv * hd), d),
            "wo": qw((L, hq * hd, d), hq * hd),
            "mlp_norm": jnp.zeros((L, d), dtype),
            "w_gate": qw((L, d, ff), d),
            "w_up": qw((L, d, ff), d),
            "w_down": qw((L, ff, d), ff),
        },
    }
    if untied:  # the split's eighth key: the tied tree's draws are unchanged
        tree["unembed"] = qw((cfg.vocab_size, d), d)
    return tree


def quantize_param_specs(specs: dict) -> dict:
    """Mirror quantize_params over a PartitionSpec pytree: every quantized
    weight's spec becomes QTensor(q=original spec, s=last-axis-only spec).

    The scale has keepdims shape [..., 1, out]: its size-1 contraction axis
    cannot be sharded, so the scale spec keeps only the spec's LAST entry
    (the output-channel sharding q and s share) and replicates the rest.
    For the vocab-sharded embedding (P(model, None)) the [1, d] scale is
    therefore fully replicated — correct, since every vocab shard needs all
    d column scales for the gather/unembed dual use."""
    from jax.sharding import PartitionSpec as P

    def qspec(spec):
        return QTensor(q=spec, s=P(*([None] * (len(spec) - 1) + [spec[-1]])))

    layers = _map_groups(
        lambda g: {k: (qspec(v) if k in _QUANT_KEYS else v) for k, v in g.items()},
        specs["layers"],
    )
    out = {
        "embed": qspec(specs["embed"]),
        "final_norm": specs["final_norm"],
        "layers": layers,
    }
    if "unembed" in specs:
        out["unembed"] = qspec(specs["unembed"])
    return out

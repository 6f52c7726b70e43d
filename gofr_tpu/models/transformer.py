"""Gemma-family decoder-only transformer, TPU-first.

Design choices (all for XLA/TPU, none inherited from the reference repo,
which contains no models — SURVEY.md §2.9):

- **Pure functions over pytrees.** Params are nested dicts of arrays; no
  module system. Sharding is a pytree of PartitionSpecs zipped over the same
  structure (gofr_tpu.parallel.sharding).
- **Layers stacked, scanned.** All layer weights carry a leading [n_layers]
  axis and the layer stack is a single `lax.scan` — one compiled layer body
  regardless of depth, which keeps compile times flat and lets XLA pipeline
  the weight streams from HBM.
- **Static shapes everywhere.** Prefill takes right-padded [batch, seq]
  buckets with a length vector; decode is a fixed-shape single-token step
  against a preallocated KV cache (ring position = per-sequence cursor).
  Data-dependent work (sampling loops) uses lax.scan / lax.while_loop.
- **bfloat16 activations & weights, float32 softmax/norms/logits.**

Gemma conventions implemented: RMSNorm applied as (1+scale), embeddings
scaled by sqrt(d_model), GeGLU MLP, RoPE, GQA/MQA, optional logit
soft-capping (Gemma 2), tied input/output embeddings.
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ..ops import (
    apply_rope,
    chunk_decode_attention,
    chunk_prefill_attention,
    decode_attention,
    multi_head_attention,
    rms_norm,
)
from .quant import QTensor, qmm, qmm_a8


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 256_000
    d_model: int = 2048
    n_layers: int = 18
    n_heads: int = 8
    n_kv_heads: int = 1
    head_dim: int = 256
    d_ff: int = 16_384
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-6
    attn_logit_cap: float = 0.0  # gemma-2 style soft-capping; 0 disables
    final_logit_cap: float = 0.0
    act: str = "gelu"  # MLP gate activation: "gelu" (Gemma) | "silu" (Llama)
    scale_embed: bool = True  # multiply embeddings by sqrt(d_model) (Gemma)
    sliding_window: int = 0  # Mistral-style local attention; 0 = global
    qkv_bias: bool = False  # Qwen2-style bias on the q/k/v projections
    # Mixture-of-experts MLP (0 = dense). Experts replace the dense GeGLU
    # with the dropless routed FFN (models.moe.routed_ffn) inside the same
    # scanned layer body. What differs between checkpoints is read here:
    # the router's score function, whether the chosen weights are
    # normalised and scaled, shared experts (`ws_*` leaves) and the experts'
    # own width (0 = d_ff). A correction bias on the choice is a leaf
    # (`router_bias`), not a field: the router adds it where the layer has it.
    n_experts: int = 0
    moe_top_k: int = 2
    moe_score: str = "softmax"  # | "sigmoid"
    moe_norm_topk: bool = False
    moe_scale: float = 1.0
    n_shared_experts: int = 0
    moe_d_ff: int = 0
    # Leading layers that keep the dense MLP before the routed ones begin:
    # params["layers"] is then a tuple of stacked groups, scanned in turn
    # (layer_groups).
    n_dense_layers: int = 0
    # Multi-head latent attention (kv_lora_rank > 0; GQA otherwise): queries
    # through a q_lora_rank bottleneck, keys and values from ONE normalized
    # latent row of kv_lora_rank values plus a rope key of qk_rope_head_dim
    # that every head shares. n_kv_heads and head_dim are then unused.
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # Layers of different kinds in one stack. `layer_windows` gives every
    # layer the keys it attends (0 = all of them); empty means every layer
    # alike, `sliding_window` (the special case every preset but one is).
    # A stack that holds both kinds is MIXED (`mixed`): it serves from the
    # paged pool, where each kind keeps what it can ever read
    # (kvcache.CacheManager). `qk_norm`: an RMSNorm over each head's values
    # of q and of k (leaves `q_norm`, `k_norm` [head_dim]) before any
    # rotation. `rope_on_window_only`: windowed layers rotate q and k, full
    # layers carry no positional encoding at all.
    layer_windows: tuple = ()
    qk_norm: bool = False
    rope_on_window_only: bool = False
    # One chip's share of the routed experts: this program HOLDS
    # `moe_held_experts` of the n_experts (0 = all of them), the first of
    # which is expert `moe_first_expert`. The router still scores and chooses
    # over all n_experts; pairs routed elsewhere are left out of the grouped
    # matmuls and of the sum (models.moe.routed_ffn), and nothing stands in
    # for the chips that hold the rest.
    moe_first_expert: int = 0
    moe_held_experts: int = 0
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.layer_windows:
            if len(self.layer_windows) != self.n_layers:
                raise ValueError(
                    f"layer_windows names {len(self.layer_windows)} layers, the "
                    f"stack has {self.n_layers}"
                )
            if len({w for w in self.layer_windows if w}) > 1:
                raise ValueError(
                    "layer_windows holds more than one window size: a stack "
                    "mixes ONE window with full attention"
                )
            if not self.mixed and self.layer_windows[0] != self.sliding_window:
                raise ValueError(
                    "every layer has the same window: say it as sliding_window"
                )
            if self.mixed and (self.latent or self.sliding_window):
                raise ValueError(
                    "a mixed stack is GQA and names its windows in layer_windows "
                    "alone (sliding_window 0)"
                )
        if self.moe_held_experts and not (
            0 <= self.moe_first_expert
            and self.moe_first_expert + self.moe_held_experts <= self.n_experts
        ):
            raise ValueError(
                f"experts [{self.moe_first_expert}, {self.moe_first_expert + self.moe_held_experts}) "
                f"are not among the model's {self.n_experts}"
            )

    @property
    def latent(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def windows(self) -> tuple:
        """The keys each layer attends (0 = all), layer by layer: the ONE
        place the pattern is read."""
        return tuple(self.layer_windows) or (self.sliding_window,) * self.n_layers

    @property
    def mixed(self) -> bool:
        """Window layers and full layers in one stack."""
        return len(set(self.windows)) > 1

    @property
    def window(self) -> int:
        """The one window size of the stack's windowed layers (0: none)."""
        return max(self.windows)

    @property
    def held_experts(self) -> int:
        """Routed experts this program holds (all of them unless told)."""
        return self.moe_held_experts or self.n_experts

    @property
    def group_sizes(self) -> tuple:
        """Layers in each stacked group of params["layers"]."""
        if self.n_experts > 0 and 0 < self.n_dense_layers < self.n_layers:
            return (self.n_dense_layers, self.n_layers - self.n_dense_layers)
        return (self.n_layers,)

    # ---- presets -------------------------------------------------------
    @staticmethod
    def gemma_2b() -> "TransformerConfig":
        return TransformerConfig()

    @staticmethod
    def gemma_7b() -> "TransformerConfig":
        return TransformerConfig(
            d_model=3072, n_layers=28, n_heads=16, n_kv_heads=16, d_ff=24_576
        )

    @staticmethod
    def llama3_8b() -> "TransformerConfig":
        """Llama-3-8B: SwiGLU MLP, GQA 32/8, untied lm_head (the loader
        adds an `unembed` leaf), plain RMSNorm (the loader stores HF's
        scale minus 1 so the shared (1+scale) kernel is exact), no
        embedding scaling. rope theta 500k."""
        return TransformerConfig(
            vocab_size=128_256, d_model=4096, n_layers=32, n_heads=32,
            n_kv_heads=8, head_dim=128, d_ff=14_336, rope_theta=500_000.0,
            norm_eps=1e-5, act="silu", scale_embed=False,
        )

    @staticmethod
    def mistral_7b() -> "TransformerConfig":
        """Mistral-7B-v0.1: Llama-shaped (SwiGLU, GQA 32/8, untied head,
        no embed scaling) plus a 4096-token sliding attention window —
        each layer attends locally, with receptive field growing by one
        window per layer."""
        return TransformerConfig(
            vocab_size=32_000, d_model=4096, n_layers=32, n_heads=32,
            n_kv_heads=8, head_dim=128, d_ff=14_336, rope_theta=10_000.0,
            norm_eps=1e-5, act="silu", scale_embed=False,
            sliding_window=4096,
        )

    @staticmethod
    def tiny_mistral(vocab_size: int = 512) -> "TransformerConfig":
        """CI-sized Mistral-style config: window 8 so sequences past 8
        tokens actually exercise the band mask."""
        return TransformerConfig(
            vocab_size=vocab_size, d_model=64, n_layers=2, n_heads=4,
            n_kv_heads=2, head_dim=16, d_ff=128, rope_theta=10_000.0,
            norm_eps=1e-5, act="silu", scale_embed=False,
            sliding_window=8, dtype=jnp.float32,
        )

    @staticmethod
    def qwen2_7b() -> "TransformerConfig":
        """Qwen2-7B: Llama-shaped (SwiGLU, GQA 28/4, untied head, no
        embed scaling) plus bias on the q/k/v projections."""
        return TransformerConfig(
            vocab_size=152_064, d_model=3584, n_layers=28, n_heads=28,
            n_kv_heads=4, head_dim=128, d_ff=18_944, rope_theta=1_000_000.0,
            norm_eps=1e-6, act="silu", scale_embed=False, qkv_bias=True,
        )

    @staticmethod
    def tiny_qwen2(vocab_size: int = 512) -> "TransformerConfig":
        """CI-sized Qwen2-style config (silu, qkv bias, no embed scale)."""
        return TransformerConfig(
            vocab_size=vocab_size, d_model=64, n_layers=2, n_heads=4,
            n_kv_heads=2, head_dim=16, d_ff=128, rope_theta=1_000_000.0,
            norm_eps=1e-6, act="silu", scale_embed=False, qkv_bias=True,
            dtype=jnp.float32,
        )

    @staticmethod
    def tiny_llama(vocab_size: int = 512) -> "TransformerConfig":
        """CI-sized Llama-style config (silu, no embed scale)."""
        return TransformerConfig(
            vocab_size=vocab_size, d_model=64, n_layers=2, n_heads=4,
            n_kv_heads=2, head_dim=16, d_ff=128, rope_theta=500_000.0,
            norm_eps=1e-5, act="silu", scale_embed=False, dtype=jnp.float32,
        )

    @staticmethod
    def tiny(vocab_size: int = 512) -> "TransformerConfig":
        """CI-sized model: runs the identical code path on CPU in ms."""
        return TransformerConfig(
            vocab_size=vocab_size, d_model=64, n_layers=2, n_heads=4,
            n_kv_heads=2, head_dim=16, d_ff=128, dtype=jnp.float32,
        )

    @staticmethod
    def tiny_latent_moe(vocab_size: int = 512) -> "TransformerConfig":
        """CI-sized latent-attention MoE: one dense layer, then two layers
        of 8 sigmoid-routed experts (top-2, normalised, scaled, correction
        bias) with a shared one; latent 32 + rope 8, 4 heads."""
        return TransformerConfig(
            vocab_size=vocab_size, d_model=64, n_layers=3, n_heads=4,
            n_kv_heads=1, head_dim=24, d_ff=128, rope_theta=1_000_000.0,
            norm_eps=1e-5, act="silu", scale_embed=False, dtype=jnp.float32,
            n_experts=8, moe_top_k=2, moe_score="sigmoid", moe_norm_topk=True,
            moe_scale=1.8, n_shared_experts=1, moe_d_ff=32,
            n_dense_layers=1, kv_lora_rank=32, q_lora_rank=48,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=24,
        )

    @staticmethod
    def tiny_mixed_moe(vocab_size: int = 512) -> "TransformerConfig":
        """CI-sized mixed stack: three window layers (8 keys, RoPE) then a
        full one (no positional encoding), q/k norm; one dense layer, then
        two periods of 16 sigmoid-routed experts (top-4, normalised, scaled,
        correction bias) with a shared one, of which this program holds
        the first 4."""
        return TransformerConfig(
            vocab_size=vocab_size, d_model=64, n_layers=9, n_heads=4,
            n_kv_heads=2, head_dim=16, d_ff=128, rope_theta=1_000_000.0,
            norm_eps=1e-5, act="silu", scale_embed=False, dtype=jnp.float32,
            layer_windows=(8, 8, 8, 0, 8, 8, 8, 0, 8), qk_norm=True,
            rope_on_window_only=True,
            n_experts=16, moe_top_k=4, moe_score="sigmoid", moe_norm_topk=True,
            moe_scale=2.5, n_shared_experts=1, moe_d_ff=32, n_dense_layers=1,
            moe_first_expert=0, moe_held_experts=4,
        )

    @staticmethod
    def tiny_moe(vocab_size: int = 512) -> "TransformerConfig":
        """CI-sized sparse config: 4 experts, top-2 routing — expert count
        divisible by TP=2/4 for the 8-virtual-device CPU mesh tests."""
        return TransformerConfig(
            vocab_size=vocab_size, d_model=64, n_layers=2, n_heads=4,
            n_kv_heads=2, head_dim=16, d_ff=128, dtype=jnp.float32,
            n_experts=4, moe_top_k=2,
        )


class KVCache(NamedTuple):
    """Preallocated per-layer KV with a per-sequence write cursor."""

    k: jnp.ndarray  # [n_layers, batch, max_len, n_kv_heads, head_dim]
    v: jnp.ndarray  # [n_layers, batch, max_len, n_kv_heads, head_dim]
    length: jnp.ndarray  # [batch] int32 — tokens written so far


def init_cache(cfg: TransformerConfig, batch: int, max_len: int) -> KVCache:
    from ..kvcache import row_shapes

    k_row, v_row = row_shapes(cfg)
    return KVCache(
        k=jnp.zeros((cfg.n_layers, batch, max_len) + k_row, cfg.dtype),
        v=jnp.zeros((cfg.n_layers, batch, max_len) + v_row, cfg.dtype),
        length=jnp.zeros((batch,), jnp.int32),
    )


def init_params(rng: jax.Array, cfg: TransformerConfig) -> dict:
    d, hd, hq, hkv, ff, L = (
        cfg.d_model, cfg.head_dim, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff, cfg.n_layers,
    )
    keys = jax.random.split(rng, 6)

    def w(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(fan_in)).astype(
            cfg.dtype
        )

    bias = (
        {
            # random (not zero) so tests exercising random-init params make
            # the bias add load-bearing, like a trained checkpoint's
            "bq": w(jax.random.fold_in(keys[1], 1), (L, hq * hd), d),
            "bkv": w(jax.random.fold_in(keys[2], 1), (L, 2 * hkv * hd), d),
        }
        if cfg.qkv_bias
        else {}
    )
    if cfg.latent:
        C, ql = cfg.kv_lora_rank, cfg.q_lora_rank
        dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        ka = jax.random.split(keys[1], 4)
        attn = {
            "attn_norm": jnp.zeros((L, d), cfg.dtype),
            "wq_a": w(ka[0], (L, d, ql), d),
            "q_norm": jnp.zeros((L, ql), cfg.dtype),
            "wq_b": w(ka[1], (L, ql, hq * (dn + dr)), ql),
            "wkv_a": w(ka[2], (L, d, C + dr), d),
            "kv_norm": jnp.zeros((L, C), cfg.dtype),
            # per head [k_nope | v]: W_uk and W_uv of the absorbed form
            "wkv_b": w(ka[3], (L, C, hq * (dn + dv)), C),
            "wo": w(keys[3], (L, hq * dv, d), hq * dv),
        }
    else:
        qk = (
            {"q_norm": jnp.zeros((L, hd), cfg.dtype), "k_norm": jnp.zeros((L, hd), cfg.dtype)}
            if cfg.qk_norm else {}
        )
        attn = {
            **bias, **qk,
            "attn_norm": jnp.zeros((L, d), cfg.dtype),
            "wq": w(keys[1], (L, d, hq * hd), d),
            "wkv": w(keys[2], (L, d, 2 * hkv * hd), d),
            "wo": w(keys[3], (L, hq * hd, d), hq * hd),
        }
    sizes = cfg.group_sizes
    dense_mlp = {} if cfg.n_experts > 0 and len(sizes) == 1 else {
        # gate and up are SEPARATE tensors, not a fused [d, 2*ff] matmul:
        # both get identical column-parallel shardings (so the
        # gelu(gate)*up product is TP-collective-free), and each matmul
        # keeps a contiguous MXU-friendly layout — a fused-then-split
        # layout costs either a mid-layer reshard (contiguous halves
        # under TP) or a ~3x decode slowdown (interleaved pairs force a
        # strided relayout; measured on v5e).
        "w_gate": w(keys[4], (L, d, ff), d),
        "w_up": w(jax.random.fold_in(keys[4], 1), (L, d, ff), d),
        "w_down": w(keys[5], (L, ff, d), ff),
    }
    if cfg.n_experts > 0:
        # Sparse MLP: experts batched on a leading E axis (the EP shard
        # axis — parallel.sharding.param_specs) plus a replicated router.
        # (the router scores all E; the stacks hold this program's share)
        E, Eh, fe = cfg.n_experts, cfg.held_experts, cfg.moe_d_ff or ff
        mlp = {
            "w_router": w(jax.random.fold_in(keys[3], 1), (L, d, E), d),
            "w_gate": w(keys[4], (L, Eh, d, fe), d),
            "w_up": w(jax.random.fold_in(keys[4], 1), (L, Eh, d, fe), d),
            "w_down": w(keys[5], (L, Eh, fe, d), fe),
        }
        if cfg.moe_score == "sigmoid":  # sigmoid scores are chosen with a correction bias
            mlp["router_bias"] = 0.01 * jax.random.normal(
                jax.random.fold_in(keys[3], 2), (L, E), jnp.float32
            )
        if cfg.n_shared_experts > 0:
            fs = fe * cfg.n_shared_experts
            ks = jax.random.split(jax.random.fold_in(keys[5], 1), 3)
            mlp.update(
                ws_gate=w(ks[0], (L, d, fs), d), ws_up=w(ks[1], (L, d, fs), d),
                ws_down=w(ks[2], (L, fs, d), fs),
            )
    else:
        mlp = dense_mlp
    stacked = {**attn, "mlp_norm": jnp.zeros((L, d), cfg.dtype)}
    if len(sizes) == 1:
        layers = {**stacked, **mlp}
    else:  # leading dense layers, then the routed ones: two stacked groups
        n0 = sizes[0]
        layers = (
            jax.tree.map(lambda a: a[:n0], {**stacked, **dense_mlp}),
            jax.tree.map(lambda a: a[n0:], {**stacked, **mlp}),
        )
    return {
        "embed": w(keys[0], (cfg.vocab_size, d), d),
        "final_norm": jnp.zeros((d,), cfg.dtype),
        "layers": layers,
    }


LAYER_KEY = "_layer"  # in a layer's params under the indexed scan: its index in the whole stack


def layer_kinds(cfg) -> tuple[tuple, tuple]:
    """A stack's layers by kind, read from cfg.windows: (the full layers,
    the windowed layers) as indices into the whole stack. A mixed stack's
    cache keeps one pool a kind, each [L_kind, ...]: a layer's place in its
    kind's pool is its place in its tuple."""
    ws = cfg.windows
    return (
        tuple(i for i, w in enumerate(ws) if not w),
        tuple(i for i, w in enumerate(ws) if w),
    )


def _kind_of(cfg, layer):
    """(is windowed, index among the layers of its kind) of the layer whose
    index in the whole stack is the traced scalar `layer`."""
    import numpy as np

    at = np.zeros((cfg.n_layers,), np.int32)
    for kind in layer_kinds(cfg):
        at[list(kind)] = np.arange(len(kind), dtype=np.int32)
    windowed = np.asarray([w > 0 for w in cfg.windows])
    return jnp.asarray(windowed)[layer], jnp.asarray(at)[layer]


def _by_kind(cfg, layer, window_fn, full_fn, *operands):
    """A mixed stack's one branch on a layer's kind: `window_fn` under the
    scope layer/attn_window, `full_fn` under layer/attn_full (a compiled
    program's op_names say which kind an operation serves). Both take
    (index among the layers of its kind, *operands)."""
    windowed, at = _kind_of(cfg, layer)

    def scoped(name, fn):
        def branch(at, *ops):
            with jax.named_scope(name):
                return fn(at, *ops)

        return branch

    return jax.lax.cond(
        windowed, scoped("layer/attn_window", window_fn),
        scoped("layer/attn_full", full_fn), at, *operands,
    )


def _expert_stacks(group: dict) -> dict:
    """The routed experts' stacks of one group, [L, E, in, out]: what the
    indexed scan leaves whole."""
    return {
        k: group[k] for k in ("w_gate", "w_up", "w_down")
        if k in group and getattr(group[k], "q", group[k]).ndim >= 4
    }


def layer_groups(layers) -> tuple:
    """params["layers"] as its stacked groups: one dict of [L, ...] leaves
    for a homogeneous stack, or a tuple of them (TransformerConfig.
    group_sizes) scanned in turn."""
    return tuple(layers) if isinstance(layers, (tuple, list)) else (layers,)


_ACTIVATIONS = {"gelu": jax.nn.gelu, "silu": jax.nn.silu}


def _layer_scan(layers: dict, layer_fn, x, rest: tuple, overlap=None, index: bool = False):
    """Scan ``layer_fn(x, lp, rest_i) -> (x, ys_i)`` over the stacked
    [n_layers, ...] weights.

    ``overlap=None`` is the plain lax.scan every path used before. With
    ``overlap`` (a pytree transform — parallel.sharding.replicate_gather
    under tensor parallelism), the scan carry DOUBLE-BUFFERS the weights:
    each step starts the all-gather of layer i+1's shards (no data
    dependency on this step's compute, so XLA's async collectives /
    latency-hiding scheduler run it behind layer i's matmuls) and
    computes layer i with the already-gathered full weights. Gathered
    compute is bit-identical to the single-device forward — no
    partial-product psum, hence no collective reduction-order drift.
    The final layer prefetches itself (clamped index); one redundant
    gather, zero extra compute.

    ``layers`` may be a tuple of stacked groups (layer_groups): each is
    scanned in turn, its layers indexing ``rest`` at their own offset (a
    dynamic index into the whole stack, as the scan's own slicing is, so no
    group's share of a cache is copied out), and the ys are joined. That
    indexed scan also serves one group that holds expert stacks
    (_expert_stacks).

    ``index=True`` (every paged decode) gives each layer its index in the
    whole stack as ``lp[LAYER_KEY]``, whichever scan runs: the paged pool is
    NOT in ``rest``; the layer's kernel reads it whole, at that index."""
    groups = layer_groups(layers)
    if len(groups) > 1 and overlap is not None:
        raise ValueError("layer groups do not run under the TP gather overlap")
    if overlap is None and (len(groups) > 1 or any(_expert_stacks(g) for g in groups)):
        from ..ops.grouped import LayerOf

        parts, l0 = [], 0
        for g in groups:
            n = jax.tree.leaves(g)[0].shape[0]
            whole = _expert_stacks(g)
            scanned = {k: v for k, v in g.items() if k not in whole}

            def body(x, xs, l0=l0, whole=whole):
                lp, i = xs
                at = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(a, l0 + i, 0, keepdims=False),
                    tuple(rest),
                )
                # expert stacks stay whole (a kernel reads its blocks at the
                # layer's index; a slice would be copied out first), as the
                # paged pool does, read at LAYER_KEY
                lp = {**lp, **{k: LayerOf(v, i) for k, v in whole.items()}, LAYER_KEY: l0 + i}
                return layer_fn(x, lp, at)

            x, ys = jax.lax.scan(body, x, (scanned, jnp.arange(n, dtype=jnp.int32)))
            parts.append(ys)
            l0 += n
        if len(parts) == 1:
            return x, parts[0]
        return x, jax.tree.map(lambda *a: jnp.concatenate(a, axis=0), *parts)
    layers = groups[0]
    L = jax.tree.leaves(layers)[0].shape[0]
    if overlap is None and index:

        def body(x, xs):
            return layer_fn(x, {**xs[0], LAYER_KEY: xs[1]}, xs[2:])

        return jax.lax.scan(body, x, (layers, jnp.arange(L, dtype=jnp.int32)) + tuple(rest))
    if overlap is None:

        def body(x, xs):
            x, ys = layer_fn(x, xs[0], xs[1:])
            return x, ys

        return jax.lax.scan(body, x, (layers,) + tuple(rest))


    def at(i):
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
            layers,
        )

    def body(carry, xs):
        x, g = carry
        g_next = overlap(at(jnp.minimum(xs[0] + 1, L - 1)))
        x, ys = layer_fn(x, {**g, LAYER_KEY: xs[0]} if index else g, xs[1:])
        return (x, g_next), ys

    (x, _), ys = jax.lax.scan(
        body,
        (x, overlap(at(0))),
        (jnp.arange(L, dtype=jnp.int32),) + tuple(rest),
    )
    return x, ys


def _act_fn(cfg: TransformerConfig):
    try:
        return _ACTIVATIONS[cfg.act]
    except KeyError:
        raise ValueError(
            f"unknown activation {cfg.act!r}; expected one of {sorted(_ACTIVATIONS)}"
        ) from None


def _lora_delta(h, lp, name, aids):
    """Per-row batched LoRA delta (h @ A[gid]) @ B[gid], f32, or None when
    this layer carries no stacked tables / no adapter ids were passed —
    the None path keeps non-LoRA engines byte-identical (the whole branch
    is static pytree structure, so XLA never sees it).

    ``lp[f"lora_{name}_a"]`` is [G, d_in, r] after the layer scan slices
    the leading L axis; ``aids`` is [rows] int32 selecting each batch
    row's adapter (gid 0 = all-zero identity tables, whose +0.0 delta
    cannot change any downstream value — gofr_tpu.lora)."""
    a = lp.get("lora_" + name + "_a")
    if a is None or aids is None:
        return None
    b = lp["lora_" + name + "_b"]
    ag = jnp.take(a, aids, axis=0)  # [rows, d_in, r]
    bg = jnp.take(b, aids, axis=0)  # [rows, r, d_out]
    t = jnp.einsum("bsd,bdr->bsr", h.astype(jnp.float32), ag)
    return jnp.einsum("bsr,bro->bso", t, bg)


def _lora_mm(mm, h, lp, name, aids):
    """Base projection plus (optional) per-row adapter delta."""
    out = mm(h, lp[name])
    d = _lora_delta(h, lp, name, aids)
    return out if d is None else out + d.astype(out.dtype)


def _mlp_block(cfg, h, lp, mm, aids=None):
    """Post-norm MLP output (the caller adds the residual) and what the
    experts did: dense GeGLU with optional per-row LoRA deltas (stats
    None), or the dropless routed FFN when the layer carries a router
    (models.moe.routed_ffn; stats = moe_layer_stats of its counts). LoRA
    skips expert weights by construction (lora.target_dims drops 4-D
    stacks), so the two features compose on attention projections."""
    if "w_router" in lp:
        from .moe import routed_ffn

        b, s, d = h.shape
        y, counts = routed_ffn(cfg, h.reshape(b * s, d), lp, mm)
        routed = b * s * cfg.moe_top_k if cfg.moe_held_experts else None
        return y.reshape(b, s, d).astype(h.dtype), moe_layer_stats(counts, routed)
    g = _lora_mm(mm, h, lp, "w_gate", aids)
    u = _lora_mm(mm, h, lp, "w_up", aids)
    y = _lora_mm(mm, _act_fn(cfg)(g) * u, lp, "w_down", aids)
    # a dense layer of a routed model (a leading group): nothing routed
    return y, (jnp.zeros((moe_stats_width(cfg),), jnp.int32) if cfg.n_experts > 0 else None)


def moe_stats_width(cfg) -> int:
    """Entries of moe_layer_stats for this config."""
    return 2 + cfg.held_experts + (1 if cfg.moe_held_experts else 0)


def moe_layer_stats(counts: jnp.ndarray, routed: int | None = None) -> jnp.ndarray:
    """One routed layer call as [pairs, experts touched, rows per expert...]
    int32: summed over a program's layer calls it is what the engine's step
    record and stats()["moe"] report. A program that holds a SHARE of the
    experts (cfg.moe_held_experts) counts the pairs it computed, its own
    experts, and appends `routed`: every pair the call's router chose, its
    own or not."""
    return jnp.concatenate(
        [jnp.sum(counts)[None], jnp.sum(counts > 0)[None].astype(jnp.int32), counts]
        + ([] if routed is None else [jnp.full((1,), routed, jnp.int32)])
    )


def _mlp_residual(cfg, x, lp, mm, aids=None):
    """x + MLP(norm(x)), and the layer's moe stats as a tuple to append to
    a scanned layer's ys: empty for a dense model, so its programs carry
    nothing new."""
    with jax.named_scope("layer/mlp"):
        h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        y, stats = _mlp_block(cfg, h, lp, mm, aids)
        x = x + y
    return x, (() if stats is None else (stats,))


def _latent_up(cfg, w):
    """wkv_b [C, hq * (nope + v)] as the absorbed form's two halves:
    (W_uk [C, hq, nope], its column scales [hq, nope] or None, W_uv
    [C, hq, v], its scales). An int8 tensor stays int8: the scale of a
    column that is CONTRACTED (W_uk's) goes onto the query instead."""
    hq, dn, dv = cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    quant = isinstance(w, QTensor)
    wq = (w.q if quant else w).reshape(cfg.kv_lora_rank, hq, dn + dv)
    sc = w.s.reshape(hq, dn + dv) if quant else None
    return (
        wq[..., :dn], None if sc is None else sc[:, :dn],
        wq[..., dn:], None if sc is None else sc[:, dn:],
    )


def _attn_block(cfg, x, lp, positions, mm, aids, attend):
    """The ONE attention block of every program: norm, projections, RoPE,
    ``attend(q, k_new, v_new) -> (attn, carry)`` (the program's own cache
    write and attention), output projection, residual. Returns (x, carry).

    GQA: q [b, s, hq, hd], k_new / v_new [b, s, hkv, hd]. Latent
    (cfg.latent), in the absorbed form the cache forces: q is
    [q_nope W_uk^T | RoPE(q_rope)] per head, zero-padded to the pool's rope
    width, k_new the normalized latent c_kv [b, s, 1, C], v_new the shared
    rotated rope key [b, s, 1, R]; ``attend`` returns o_lat [b, s, hq, C]
    and W_uv is applied here. LoRA on the latent projections is refused at
    engine build."""
    b, s, _ = x.shape
    hq = cfg.n_heads
    if cfg.latent:
        from ..ops import latent_rope_width

        C, dn, dr = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        pad = latent_rope_width(dr) - dr
        with jax.named_scope("layer/attn_latent"):
            h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
            cq = rms_norm(mm(h, lp["wq_a"]), lp["q_norm"], cfg.norm_eps)
            q = mm(cq, lp["wq_b"]).reshape(b, s, hq, dn + dr)
            kv = mm(h, lp["wkv_a"])  # [b, s, C + dr]
            c_kv = rms_norm(kv[..., :C], lp["kv_norm"], cfg.norm_eps)[:, :, None, :]
            k_rope = apply_rope(kv[:, :, None, C:], positions, cfg.rope_theta)
            q_rope = apply_rope(q[..., dn:], positions, cfg.rope_theta)
            w_uk, s_uk, w_uv, s_uv = _latent_up(cfg, lp["wkv_b"])
            q_nope = q[..., :dn] if s_uk is None else q[..., :dn] * s_uk.astype(q.dtype)
            q_abs = jnp.einsum(
                "bshn,chn->bshc", q_nope, w_uk.astype(q.dtype),
                preferred_element_type=jnp.float32,
            ).astype(q.dtype)
            widen = ((0, 0), (0, 0), (0, 0), (0, pad))
            q = jnp.concatenate([q_abs, jnp.pad(q_rope, widen)], axis=-1)
            k_rope = jnp.pad(k_rope, widen)
        o_lat, carry = attend(q, c_kv, k_rope)
        with jax.named_scope("layer/attn_latent"):
            o = jnp.einsum(
                "bshc,chv->bshv", o_lat, w_uv.astype(o_lat.dtype),
                preferred_element_type=jnp.float32,
            )
            if s_uv is not None:
                o = o * s_uv.astype(jnp.float32)
            o = o.astype(x.dtype).reshape(b, s, hq * cfg.v_head_dim)
        with jax.named_scope("layer/attn"):
            x = x + mm(o, lp["wo"]).astype(x.dtype)
        return x, carry
    hkv, hd = cfg.n_kv_heads, cfg.head_dim
    with jax.named_scope("layer/attn"):
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q = _lora_mm(mm, h, lp, "wq", aids)
        if cfg.qkv_bias:  # Qwen2: bias rides the flat output (pre-reshape)
            q = q + lp["bq"].astype(q.dtype)
        q = q.reshape(b, s, hq, hd)
        # wkv packs heads OUTERMOST ([hkv, 2, hd] per output column block) so a
        # TP shard of the flat output dim holds whole (k, v) head pairs — keeps
        # Megatron column-parallel layout collective-free inside the layer.
        kv = _lora_mm(mm, h, lp, "wkv", aids)
        if cfg.qkv_bias:
            kv = kv + lp["bkv"].astype(kv.dtype)
        kv = kv.reshape(b, s, hkv, 2, hd)
        k_new, v_new = kv[:, :, :, 0], kv[:, :, :, 1]
        if cfg.qk_norm:  # over each head's values, before any rotation
            q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
            k_new = rms_norm(k_new, lp["k_norm"], cfg.norm_eps)
        if cfg.rope_on_window_only and cfg.mixed:
            # a full layer carries no positional encoding: position 0 is the
            # identity rotation (cos 1, sin 0), exactly
            positions = jnp.where(_kind_of(cfg, lp[LAYER_KEY])[0], positions, 0)
        if not cfg.rope_on_window_only or cfg.window:
            q = apply_rope(q, positions, cfg.rope_theta)
            k_new = apply_rope(k_new, positions, cfg.rope_theta)
    # Gemma queries are scaled by 1/sqrt(head_dim) (applied inside attention).
    attn, carry = attend(q, k_new, v_new)
    with jax.named_scope("layer/attn"):
        x = x + _lora_mm(mm, attn.reshape(b, s, hq * hd), lp, "wo", aids).astype(
            x.dtype
        )
    return x, carry


def _latent_scale(cfg) -> float:
    return 1.0 / (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** 0.5


def _layer_body(
    cfg: TransformerConfig,
    x: jnp.ndarray,  # [b, s, d]
    lp: dict,  # one layer's params (no leading L axis)
    positions: jnp.ndarray,  # [b, s]
    *,
    k_cache: jnp.ndarray | None,  # [b, max_len, hkv, hd] or None
    v_cache: jnp.ndarray | None,
    cache_length: jnp.ndarray | None,  # [b]
    decode: bool,
    prefill_attn=None,  # optional (q, k, v) -> attn override (ring/SP path)
    aids: jnp.ndarray | None = None,  # [b] int32 per-row adapter ids (LoRA)
):
    # Prefill (many token rows, MXU-bound) uses the W8A8 integer dot when
    # weights are quantized; decode (one row, HBM-bound) dequantizes into
    # the dot. Plain-array weights are unaffected by either.
    mm = qmm if decode else qmm_a8

    def attend(q, k, v):
        if cfg.latent:
            if decode or prefill_attn is not None:
                raise ValueError(
                    "latent attention serves through the paged engine "
                    "(decode_chunk_paged / prefill_append); this forward pass "
                    "is its whole-sequence form only"
                )
            from ..ops import latent_chunk_prefill_attention

            zero = jnp.zeros((x.shape[0],), jnp.int32)
            attn = latent_chunk_prefill_attention(
                q, k, v, zero, scale=_latent_scale(cfg)
            )
            return attn, (k, v)
        if cfg.mixed:
            if decode or prefill_attn is not None:
                raise ValueError(
                    "a mixed stack (window and full layers) serves through the "
                    "paged engine (decode_chunk_paged / prefill_append); this "
                    "forward pass is its whole-sequence form only"
                )

            def whole(window):
                return lambda _at, q, k, v: multi_head_attention(
                    q, k, v, causal=True, logit_cap=cfg.attn_logit_cap, window=window
                )

            attn = _by_kind(cfg, lp[LAYER_KEY], whole(cfg.window), whole(0), q, k, v)
            return attn, (k, v)
        if decode:
            # Write this step's k/v at each sequence's cursor, then attend over
            # the valid prefix. vmap'd dynamic_update_slice = per-batch scatter.
            upd = jax.vmap(lambda c, n, i: jax.lax.dynamic_update_slice(c, n, (i, 0, 0)))
            kc = upd(k_cache, k.astype(k_cache.dtype), cache_length)
            vc = upd(v_cache, v.astype(v_cache.dtype), cache_length)
            attn = decode_attention(
                q, kc, vc, cache_length + 1,
                logit_cap=cfg.attn_logit_cap, window=cfg.sliding_window,
            )
            return attn, (kc, vc)
        # Right-padded prompts need no kv_mask here: pads sit AFTER real
        # tokens, so causal masking already hides them from every real query;
        # pad-position outputs are discarded (loss-masked / never read) and
        # pad K/V in the cache is masked by cache.length at decode. Keeping
        # the call dense is what lets the Pallas flash kernel engage.
        if prefill_attn is not None:
            attn = prefill_attn(q, k, v)
        else:
            attn = multi_head_attention(
                q, k, v, causal=True, logit_cap=cfg.attn_logit_cap,
                window=cfg.sliding_window,
            )
        # Prefill fills the cache from position 0 (right-padded batches).
        return attn, (k, v)

    x, (new_k, new_v) = _attn_block(cfg, x, lp, positions, mm, aids, attend)
    x, _stats = _mlp_residual(cfg, x, lp, mm, aids)
    return x, new_k, new_v


def transformer_forward(
    params: dict,
    cfg: TransformerConfig,
    tokens: jnp.ndarray,  # [b, s] int32
    positions: jnp.ndarray,  # [b, s] int32
    *,
    cache: KVCache | None = None,
    kv_mask: jnp.ndarray | None = None,  # [b, s] True = real token (prefill)
    decode: bool = False,
    unembed_positions: jnp.ndarray | None = None,  # [b] -> logits only there
    prefill_attn=None,  # optional attention override for the prefill path
    aids: jnp.ndarray | None = None,  # [b] int32 per-row adapter ids (LoRA)
) -> tuple[jnp.ndarray, KVCache | None]:
    """Returns (logits float32, updated cache or None).

    logits is [b, s, vocab], or [b, 1, vocab] when unembed_positions is
    given — serving prefill only needs last-token logits, and skipping the
    full [b, s, vocab] unembed saves seq_len x the memory/FLOPs of the
    single biggest matmul (vocab 256k: 8.4 GB at b=64, s=128)."""
    x = _embed_tokens(params, cfg, tokens)

    if decode:
        assert cache is not None

        def layer(x, lp, rest):
            kc, vc = rest
            x, nk, nv = _layer_body(
                cfg, x, lp, positions,
                k_cache=kc, v_cache=vc, cache_length=cache.length, decode=True,
                aids=aids,
            )
            return x, (nk, nv)

        x, (ks, vs) = _layer_scan(params["layers"], layer, x, (cache.k, cache.v))
        new_cache = KVCache(k=ks, v=vs, length=cache.length + 1)
    else:

        def layer(x, lp, _rest):
            x, nk, nv = _layer_body(
                cfg, x, lp, positions,
                k_cache=None, v_cache=None, cache_length=None, decode=False,
                prefill_attn=prefill_attn, aids=aids,
            )
            return x, (nk, nv)

        x, (ks, vs) = _layer_scan(params["layers"], layer, x, (), index=cfg.mixed)
        if cache is not None:
            max_len = cache.k.shape[2]
            s = tokens.shape[1]
            pad = [(0, 0), (0, 0), (0, max_len - s), (0, 0), (0, 0)]
            lengths = (
                kv_mask.sum(axis=-1).astype(jnp.int32)
                if kv_mask is not None
                else jnp.full((tokens.shape[0],), s, jnp.int32)
            )
            new_cache = KVCache(
                k=jnp.pad(ks.astype(cache.k.dtype), pad),
                v=jnp.pad(vs.astype(cache.v.dtype), pad),
                length=lengths,
            )
        else:
            new_cache = None

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if unembed_positions is not None:
        x = jnp.take_along_axis(
            x, unembed_positions[:, None, None].astype(jnp.int32), axis=1
        )  # [b, 1, d]
    return _unembed(params, cfg, x), new_cache


def prefill(
    params: dict,
    cfg: TransformerConfig,
    tokens: jnp.ndarray,  # [b, s] right-padded
    lengths: jnp.ndarray,  # [b]
    max_cache_len: int,
    *,
    prefill_attn=None,
) -> tuple[jnp.ndarray, KVCache]:
    """Process prompts, build the KV cache, return last-token logits [b, vocab]."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    kv_mask = positions < lengths[:, None]
    cache = init_cache(cfg, b, max_cache_len)
    logits, new_cache = transformer_forward(
        params, cfg, tokens, positions, cache=cache, kv_mask=kv_mask,
        unembed_positions=lengths - 1, prefill_attn=prefill_attn,
    )
    return logits[:, 0], new_cache


def decode_step(
    params: dict,
    cfg: TransformerConfig,
    tokens: jnp.ndarray,  # [b] last sampled token per sequence
    cache: KVCache,
) -> tuple[jnp.ndarray, KVCache]:
    """One token step for every sequence in the batch. [b] -> logits [b, vocab].

    Precondition: every cache.length < max_len. dynamic_update_slice clamps
    out-of-bounds starts, so a full cache would silently overwrite the last
    slot — callers (the serving scheduler, generate) must bound steps by the
    cache capacity; gofr_tpu.datasource.tpu enforces this at admission."""
    positions = cache.length[:, None]
    logits, new_cache = transformer_forward(
        params, cfg, tokens[:, None], positions, cache=cache, decode=True
    )
    return logits[:, 0], new_cache


@jax.named_scope("embed")
def _embed_tokens(params: dict, cfg: TransformerConfig, tokens: jnp.ndarray) -> jnp.ndarray:
    """(possibly int8) embedding gather + Gemma sqrt(d) scaling."""
    emb = params["embed"]
    if isinstance(emb, QTensor):
        # int8 embedding: gather rows of q, apply the shared per-d-column
        # scale (quant.py docstring) — reads vocab x d bytes at int8 width.
        x = emb.q[tokens].astype(cfg.dtype) * emb.s.astype(cfg.dtype)
    else:
        x = emb[tokens].astype(cfg.dtype)
    if not cfg.scale_embed:
        return x
    return x * jnp.sqrt(jnp.asarray(cfg.d_model, jnp.float32)).astype(cfg.dtype)


def _unembed(params: dict, cfg: TransformerConfig, x: jnp.ndarray) -> jnp.ndarray:
    """(possibly int8) unembed for [b, s, d] -> [b, s, vocab] f32.
    Tied by default; an `unembed` leaf ([vocab, d], Llama lm_head) wins
    when present — same stored layout as embed so the int8 path is
    identical."""
    emb = params.get("unembed", params["embed"])
    if isinstance(emb, QTensor):
        # Fold the d-column scale into the activations, then one bf16 x
        # int8 dot (x*s) @ q.T — the big [vocab, d] stream stays int8.
        logits = ((x * emb.s.astype(cfg.dtype)) @ emb.q.T.astype(cfg.dtype)).astype(
            jnp.float32
        )
    else:
        logits = (x @ emb.T.astype(cfg.dtype)).astype(jnp.float32)
    if cfg.final_logit_cap > 0.0:
        logits = cfg.final_logit_cap * jnp.tanh(logits / cfg.final_logit_cap)
    return logits


def _unembed_last(params: dict, cfg: TransformerConfig, x: jnp.ndarray) -> jnp.ndarray:
    """final norm + tied unembed for a [b, 1, d] tail -> [b, vocab]."""
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return _unembed(params, cfg, x)[:, 0]


def _chunk_buffer_write(kb_l, vb_l, k_new, v_new, k_i):
    """This step's new rows at the uniform position `k_i` of the chunk's
    small buffers (decode_chunk's docstring says why)."""
    with jax.named_scope("layer/kv_write"):
        kb_l = jax.lax.dynamic_update_slice(kb_l, k_new.astype(kb_l.dtype), (0, k_i, 0, 0))
        vb_l = jax.lax.dynamic_update_slice(vb_l, v_new.astype(vb_l.dtype), (0, k_i, 0, 0))
    return kb_l, vb_l


def _land_rows(stack, rows, slots, idx):
    """Write `rows` [L, n, c, hkv, hd] into a contiguous stack
    [L, S, C, hkv, hd] WHERE IT LIES: row (l, i, j) lands at
    (l, slots[i], idx[i, j]) by ONE scatter over the stored shape, so a
    donated stack is updated in place and no whole slot, layer or stack is
    copied, transposed or restacked on the way (PERF.md §3, the contiguous
    layouts). An entry whose slot or index is out of range is dropped: a
    padding lane carries slot = S, a position past n_new the index C. Only
    unsharded axes are indexed (a TP mesh shards hkv)."""
    layers = jnp.arange(stack.shape[0], dtype=jnp.int32)[:, None, None]
    return stack.at[layers, slots[None, :, None], idx[None]].set(
        rows.astype(stack.dtype), mode="drop"
    )


def _note_moe(moe_out: list | None, stats) -> None:
    """Hand a routed model's summed moe_layer_stats to the caller's list
    (a trace-time side channel beside the program's own results: the
    engine's programs return it, nothing else asks). `stats` is empty for a
    dense model; arrays with leading axes are summed over them."""
    if moe_out is not None:
        for st in stats:
            moe_out.append(st.reshape(-1, st.shape[-1]).sum(axis=0))


@jax.named_scope("decode_chunk")
def decode_chunk(
    params: dict,
    cfg: TransformerConfig,
    tokens: jnp.ndarray,  # [b] last sampled token per sequence
    cache: KVCache,
    active: jnp.ndarray,  # [b] bool — only active slots advance their cursor
    temps: jnp.ndarray,  # [b] f32 sampling temperatures
    rng: jax.Array,
    *,
    n_steps: int,
    sample_fn,  # (logits [b, vocab] f32, temps [b], key) -> tokens [b] int32
    unroll: int = 1,  # outer-scan unroll (XLA overlaps step boundaries)
    ring: int = 0,  # >0: cache is a rolling ring of this capacity (kvcache)
    overlap=None,  # TP collective-compute overlap (see _layer_scan)
    sample_state=None,  # stateful sampler: carried pytree (see below)
    moe_out: list | None = None,  # a routed model appends its moe stats (_note_moe)
) -> tuple[jnp.ndarray, jnp.ndarray, KVCache, jax.Array]:
    """n_steps fused decode steps — the serving engine's hot loop.

    Unlike a scan over decode_step, the main KV cache is READ-ONLY inside
    the chunk: each step writes its new K/V at the UNIFORM position `step`
    of a small [L, b, n_steps, hkv, hd] ring buffer (one aligned
    dynamic_update_slice), and attention spans cache+buffer with a joint
    softmax (ops.chunk_decode_attention). The buffer is merged into
    per-slot cursor positions ONCE at chunk end, so the scan never writes
    the cache at per-sequence cursors and never restacks it through its
    outputs (the first Gemma runs on a v5e measured that at six times the
    attention math). What the merge is now: on the rolling ring ONE scatter
    over the stored `[L, S, C, hkv, hd]` shape (_land_rows), which XLA
    runs in place on the donated stack; before PR 34 it was a vmap over the
    slot axis, which compiled to a transpose of the whole stack in and out
    around a write that was not in place: 13 of the 19 ms that passes over
    whole rings took of a 130 ms decode half at mistral-7b's sizes (PERF.md
    §5, §6 PR 34; the rest lays the ring out as the scan's dots read it, and
    is still there). The dense slab keeps its per-slot
    dynamic_update_slice, which the pool's off-TPU decode shares.

    ALL slots run every step (no per-step freeze): inactive slots sample
    garbage the host discards, and only active slots' lengths advance at
    the merge. Callers must guarantee active slots have n_steps of cache
    headroom (LLMEngine caps max_new_tokens at submit).

    ring > 0 declares the cache a window-bounded ROLLING buffer of that
    capacity (gofr_tpu.kvcache): attention masks derive from reconstructed
    absolute positions, the end-of-chunk merge wraps modulo the capacity,
    and lengths keep counting ABSOLUTE tokens (RoPE positions stay exact).
    Requires cfg.sliding_window > 0 and ring >= sliding_window + n_steps
    so a merge can never overwrite a row still inside any later window.

    With ``sample_state`` (any pytree), the sampler is STATEFUL:
    ``sample_fn(logits, temps, key, state) -> (tokens, state)`` and the
    state threads through the chunk's scan — this is the seam
    grammar-constrained decoding rides (gofr_tpu.structured: per-slot
    DFA states advance with each sampled token INSIDE the fused chunk,
    where the host cannot see intermediate tokens). The final state is
    appended to the return tuple.

    Returns (tokens [n_steps, b], last [b], new cache, rng)
    [+ sample_state when one was passed].
    """
    if cfg.latent:
        raise ValueError(
            "latent attention has no contiguous decode chunk: it serves from "
            "the paged pool (decode_chunk_paged)"
        )
    if cfg.mixed:
        raise ValueError(
            "a mixed stack (window and full layers) has no contiguous decode "
            "chunk: it serves from the paged pools (decode_chunk_paged)"
        )
    L, b = cfg.n_layers, tokens.shape[0]
    max_len = cache.k.shape[2]
    K = n_steps
    # LoRA engines carry per-slot adapter ids beside the weights; chunk
    # lanes ARE engine slots, so the vector applies row-for-row (absent on
    # plain engines — static pytree structure, program unchanged).
    aids = params.get("aids")
    kb0 = jnp.zeros((L, b, K) + cache.k.shape[3:], cache.k.dtype)
    vb0 = jnp.zeros((L, b, K) + cache.v.shape[3:], cache.v.dtype)
    rng, sub = jax.random.split(rng)
    keys = jax.random.split(sub, K)
    def step(carry, inp):
        tok, kb, vb, sstate = carry
        k_i, key = inp
        positions = (cache.length + k_i)[:, None]  # [b, 1]
        x = _embed_tokens(params, cfg, tok[:, None])

        def layer(x, lp, rest):
            kc_l, vc_l, kb_l, vb_l = rest

            def attend(q, k_new, v_new):
                kb_n, vb_n = _chunk_buffer_write(kb_l, vb_l, k_new, v_new, k_i)
                with jax.named_scope("layer/attn"):
                    attn = chunk_decode_attention(
                        q, kc_l, vc_l, kb_n, vb_n, cache.length, k_i,
                        logit_cap=cfg.attn_logit_cap, window=cfg.sliding_window,
                        ring=ring,
                    )
                return attn, (kb_n, vb_n)

            x, bufs = _attn_block(cfg, x, lp, positions, qmm, aids, attend)
            x, stats = _mlp_residual(cfg, x, lp, qmm, aids)
            return x, bufs + stats

        x, ys = _layer_scan(
            params["layers"], layer, x, (cache.k, cache.v, kb, vb),
            overlap=overlap,
        )
        kb, vb = ys[:2]
        with jax.named_scope("unembed_sample"):
            logits = _unembed_last(params, cfg, x)
            if sample_state is None:
                nt = sample_fn(logits, temps, key).astype(jnp.int32)
            else:
                nt, sstate = sample_fn(logits, temps, key, sstate)
                nt = nt.astype(jnp.int32)
        return (nt, kb, vb, sstate), (nt,) + tuple(y.sum(axis=0) for y in ys[2:])

    (last, kb, vb, out_state), (toks, *stats) = jax.lax.scan(
        step, (tokens, kb0, vb0, sample_state),
        (jnp.arange(K, dtype=jnp.int32), keys),
        unroll=unroll,
    )
    _note_moe(moe_out, stats)

    if ring > 0:
        # rolling merge: the chunk's K rows land at (length + i) mod C —
        # overwriting exactly the K OLDEST resident positions, which the
        # capacity bound (C >= window + K) guarantees are already outside
        # every later query's window. Indices are distinct (K <= C), so
        # the scatter is order-independent. Garbage rows written for
        # inactive slots are harmless: a free slot is rewritten wholesale
        # at admission, and lengths (hence masks) never advance for them.
        # One scatter at (layer, slot, row) over the stack as it is stored.
        idx = jnp.mod(
            cache.length[:, None] + jnp.arange(K, dtype=jnp.int32), ring
        )  # [b, K]
        slots = jnp.arange(b, dtype=jnp.int32)
        new_k = _land_rows(cache.k, kb, slots, idx)
        new_v = _land_rows(cache.v, vb, slots, idx)
        # lengths stay ABSOLUTE (positions/RoPE/window math need them);
        # the engine's submit() cap bounds them by max_seq_len + a chunk
        new_len = jnp.where(active, cache.length + K, cache.length)
        out = (toks, last, KVCache(k=new_k, v=new_v, length=new_len), rng)
        return out if sample_state is None else out + (out_state,)

    # merge: one scatter per chunk. Inactive slots write garbage rows at a
    # clamped in-bounds start — harmless, their rows sit beyond the valid
    # length (or the slot is free and rewritten wholesale at admission).
    start = jnp.minimum(cache.length, max_len - K)
    merge = jax.vmap(
        lambda c, u, i: jax.lax.dynamic_update_slice(c, u, (0, i, 0, 0)),
        in_axes=(1, 1, 0), out_axes=1,
    )
    new_k = merge(cache.k, kb, start)
    new_v = merge(cache.v, vb, start)
    new_len = jnp.where(active, jnp.minimum(cache.length + K, max_len), cache.length)
    out = (toks, last, KVCache(k=new_k, v=new_v, length=new_len), rng)
    return out if sample_state is None else out + (out_state,)


@jax.named_scope("decode_chunk")
def decode_chunk_paged(
    params: dict,
    cfg: TransformerConfig,
    tokens: jnp.ndarray,  # [b] last sampled token per sequence
    pool: KVCache,  # k/v [L, NB, B, hkv * hd] block pool as stored; length [b]
    scales: jnp.ndarray | None,  # [2, L, NB, B, hkv] f32 (int8 pool) or None
    tables: jnp.ndarray,  # [b, MB] int32 — logical block -> pool block
    active: jnp.ndarray,  # [b] bool — only active slots advance/write
    temps: jnp.ndarray,  # [b] f32 sampling temperatures
    rng: jax.Array,
    *,
    n_steps: int,
    sample_fn,
    block: int,
    use_kernel: bool | None = None,
    interpret: bool = False,
    overlap=None,  # TP collective-compute overlap (see _layer_scan)
    sample_state=None,  # stateful sampler (see decode_chunk)
    mesh=None,  # TP mesh: the paged kernel runs per head shard (ops.attention)
    moe_out: list | None = None,  # a routed model appends its moe stats (_note_moe)
) -> tuple[jnp.ndarray, jnp.ndarray, KVCache, jnp.ndarray | None, jax.Array]:
    """decode_chunk against a BLOCK-PAGED pool (gofr_tpu.kvcache.paged).

    Same fused-chunk structure as decode_chunk — the pool is read-only
    inside the chunk, each step's K/V lands at the uniform position
    `step` of the small per-chunk buffer, one merge at chunk end — but
    the main-region attention READS THROUGH THE BLOCK TABLE
    (ops.paged_chunk_decode_attention: Pallas paged kernel on TPU,
    dense-gather fallback elsewhere) and the merge scatters the chunk's
    rows through the table into pool blocks. The pool reaches its kernel as
    the engine stores it: the layer scan carries the chunk's buffers and
    each layer's INDEX, the layers close over the whole pool and read it
    there, so no iteration slices a layer's pool out or lays it out again
    (a custom call's operand is a whole array: either would be written to
    HBM before every one of L x n_steps kernel calls). Write indices derive from
    DEVICE lengths, so pipelined dispatches and speculative rollbacks
    can never mis-aim a write; `active` must already exclude slots whose
    request retired (their table entries may point at reassigned
    blocks — the engine passes its host-side liveness mask, where the
    contiguous path could afford clamped garbage writes).

    Greedy outputs are token-identical to decode_chunk on the gathered
    dense view: every (query, key) pair sees the same dot products and
    the same positional masks, only the storage layout differs.

    Returns (tokens [n_steps, b], last [b], pool', scales', rng).
    """
    from ..kvcache import row_shapes
    from ..kvcache.paged import scatter_rows, scatter_rows_by_kind, split_tables
    from ..ops import mla_paged_chunk_decode_attention, paged_chunk_decode_attention

    L, b = cfg.n_layers, tokens.shape[0]
    K = n_steps
    aids = params.get("aids")  # per-slot adapter ids (see decode_chunk)
    quant = scales is not None and scales.size > 0
    if cfg.mixed:
        # pool.k / pool.v are (full layers' pool, window layers' pool), the
        # tables the two kinds' side by side (kvcache.CacheManager)
        kind_tables = split_tables(tables)
        tables = kind_tables[0]
    k_row, v_row = row_shapes(cfg)
    kb0 = jnp.zeros((L, b, K) + k_row, cfg.dtype)
    vb0 = jnp.zeros((L, b, K) + v_row, cfg.dtype)
    rng, sub = jax.random.split(rng)
    keys = jax.random.split(sub, K)
    ks_all = scales[0] if quant else None  # [L, NB, B, hkv]
    vs_all = scales[1] if quant else None

    def step(carry, inp):
        tok, kb, vb, sstate = carry
        k_i, key = inp
        positions = (pool.length + k_i)[:, None]  # [b, 1]
        x = _embed_tokens(params, cfg, tok[:, None])

        def layer(x, lp, rest):
            kb_l, vb_l = rest

            def attend(q, k_new, v_new):
                kb_n, vb_n = _chunk_buffer_write(kb_l, vb_l, k_new, v_new, k_i)
                # the whole pool and the layer's index: the kernel copies its
                # pages from there, no layer's pool is sliced out
                if cfg.mixed:
                    # two pools and two tables, the layer's kind chooses: a
                    # window layer reads the band [hi - window, hi) of its
                    # own (bounded) pool, under a kernel name of its own
                    def kind(i, window, name):
                        return lambda at, q, kb_n, vb_n: paged_chunk_decode_attention(
                            q, pool.k[i], pool.v[i], kind_tables[i], kb_n, vb_n,
                            pool.length, k_i, layer=at,
                            logit_cap=cfg.attn_logit_cap, window=window,
                            use_kernel=use_kernel, interpret=interpret, name=name,
                        )

                    attn = _by_kind(
                        cfg, lp[LAYER_KEY], kind(1, cfg.window, "paged_decode_window"),
                        kind(0, 0, "paged_decode"), q, kb_n, vb_n,
                    )
                    return attn, (kb_n, vb_n)
                with jax.named_scope("layer/attn"):
                    if cfg.latent:
                        attn = mla_paged_chunk_decode_attention(
                            q, pool.k, pool.v, tables, kb_n, vb_n, pool.length, k_i,
                            scale=_latent_scale(cfg), layer=lp[LAYER_KEY],
                            use_kernel=use_kernel, interpret=interpret,
                        )
                    else:
                        attn = paged_chunk_decode_attention(
                            q, pool.k, pool.v, tables, kb_n, vb_n, pool.length, k_i,
                            layer=lp[LAYER_KEY],
                            logit_cap=cfg.attn_logit_cap, window=cfg.sliding_window,
                            k_scales=ks_all, v_scales=vs_all,
                            use_kernel=use_kernel, interpret=interpret, mesh=mesh,
                        )
                return attn, (kb_n, vb_n)

            x, bufs = _attn_block(cfg, x, lp, positions, qmm, aids, attend)
            x, stats = _mlp_residual(cfg, x, lp, qmm, aids)
            return x, bufs + stats

        x, ys = _layer_scan(
            params["layers"], layer, x, (kb, vb), overlap=overlap, index=True
        )
        kb, vb = ys[:2]
        with jax.named_scope("unembed_sample"):
            logits = _unembed_last(params, cfg, x)
            if sample_state is None:
                nt = sample_fn(logits, temps, key).astype(jnp.int32)
            else:
                nt, sstate = sample_fn(logits, temps, key, sstate)
                nt = nt.astype(jnp.int32)
        return (nt, kb, vb, sstate), (nt,) + tuple(y.sum(axis=0) for y in ys[2:])

    (last, kb, vb, out_state), (toks, *stats) = jax.lax.scan(
        step, (tokens, kb0, vb0, sample_state),
        (jnp.arange(K, dtype=jnp.int32), keys),
    )
    _note_moe(moe_out, stats)

    # merge: the chunk's K rows scatter through the table at positions
    # [length, length + K) — private (refcount-1) blocks by the engine's
    # seed/COW construction, so no shared block is ever written
    cap = tables.shape[1] * block
    pos = pool.length[:, None] + jnp.arange(K, dtype=jnp.int32)[None, :]
    valid = active[:, None] & (pos < cap)
    if cfg.mixed:  # each kind's layers' rows through that kind's table
        k2, v2 = scatter_rows_by_kind(
            pool.k, pool.v, kind_tables, layer_kinds(cfg), kb, vb, pos, valid
        )
        sc2 = None
    else:
        k2, v2, sc2 = scatter_rows(
            pool.k, pool.v, tables, kb, vb, pos, valid,
            scales=(scales if quant else None),
        )
    new_len = jnp.where(active, jnp.minimum(pool.length + K, cap), pool.length)
    out = (
        toks, last, KVCache(k=k2, v=v2, length=new_len),
        (sc2 if quant else scales), rng,
    )
    return out if sample_state is None else out + (out_state,)


def _append_forward(
    params: dict,
    cfg: TransformerConfig,
    tokens: jnp.ndarray,  # [b, c]
    cache: KVCache,  # [L, b, capacity, hkv, hd] slot rows (gathered)
    cursors: jnp.ndarray,  # [b] int32 — tokens already resident
    n_new: jnp.ndarray,  # [b] int32 — valid tokens in this chunk (<= c)
    *,
    ring: int = 0,
    aids: jnp.ndarray | None = None,  # [b] int32 per-row adapter ids (LoRA)
    mesh=None,  # TP mesh: the flash kernel runs per head shard (ops.attention)
    moe_out: list | None = None,  # a routed model appends its moe stats (_note_moe)
    slots: jnp.ndarray | None = None,  # [b] int32: `cache` is the whole stack, lane i appends to slot slots[i]
) -> tuple[jnp.ndarray, tuple[jnp.ndarray, jnp.ndarray]]:
    """Shared write-then-attend chunk append (prefill_append and
    verify_chunk): write the chunk's K/V rows at the per-sequence cursor,
    attend over all resident keys + the chunk's causal triangle, return
    the final hidden states [b, c, d] plus the updated (k, v) stacks.

    Two callers, told apart by ``slots``. The paged pool (slots=None) hands
    a view it GATHERED, [L, b, capacity, hkv, hd], one lane a row; the
    layers write into it and it comes back whole, for the caller to scatter
    through its tables. The contiguous layouts (llm_programs._Slab) hand the
    engine's own stack [L, S, capacity, hkv, hd] and the lanes' slots: each
    layer gathers its lanes' rows out of ITS layer (the copy that
    write-then-attend needs anyway, a layer at a time), the scan yields only
    the chunk's rows [L, b, c, hkv, hd], and ONE scatter lands them in the
    stack where it lies (_land_rows): no whole slot is taken out, restacked
    by the scan or written back. A lane whose slot is out of range (padding)
    reads a real slot, clipped, and writes nothing.

    ``aids`` is EXPLICIT here (unlike the decode chunks, which read
    params["aids"] directly): the unified step ops prefill a PACKED
    subset of engine slots, so the caller gathers the per-slot vector
    down to the rows actually present."""
    from ..ops import latent_chunk_prefill_attention

    b, c = tokens.shape
    positions = cursors[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    i = jnp.arange(c, dtype=jnp.int32)[None, :]
    if cfg.mixed:
        return _append_forward_mixed(
            params, cfg, tokens, cache, cursors, positions, i < n_new[:, None],
            aids=aids, moe_out=moe_out,
        )
    capacity = cache.k.shape[2]
    idx = positions if ring <= 0 else jnp.mod(positions, ring)
    # out-of-bounds scatter indices are dropped (jax .at[] default), which
    # both masks the padding lanes and makes an overfull dense cache
    # impossible to corrupt
    idx = jnp.where(i < n_new[:, None], idx, capacity)
    mm = qmm_a8  # many token rows, MXU-bound: W8A8 like monolithic prefill

    x = _embed_tokens(params, cfg, tokens)

    def layer(x, lp, rest):
        kc, vc = rest  # [b, capacity, hkv, hd]; with `slots` the layer's [S, capacity, hkv, hd]

        def attend(q, k_new, v_new):
            with jax.named_scope("layer/kv_write"):
                kv, vv = kc, vc
                if slots is not None:
                    kv = jnp.take(kc, slots, axis=0, mode="clip")
                    vv = jnp.take(vc, slots, axis=0, mode="clip")
                write = jax.vmap(lambda cb, ub, ib: cb.at[ib].set(ub))
                k_new = k_new.astype(kc.dtype)
                kc2 = write(kv, k_new, idx)
                v_new = v_new.astype(vc.dtype)
                vc2 = write(vv, v_new, idx)
            with jax.named_scope("layer/attn"):
                if cfg.latent:
                    attn = latent_chunk_prefill_attention(
                        q, kc2, vc2, cursors, scale=_latent_scale(cfg)
                    )
                else:
                    attn = chunk_prefill_attention(
                        q, kc2, vc2, cursors,
                        logit_cap=cfg.attn_logit_cap, window=cfg.sliding_window,
                        ring=ring, mesh=mesh,
                    )
            return attn, ((kc2, vc2) if slots is None else (k_new, v_new))

        x, rows = _attn_block(cfg, x, lp, positions, mm, aids, attend)
        x, stats = _mlp_residual(cfg, x, lp, mm, aids)
        return x, rows + stats

    x, ys = _layer_scan(params["layers"], layer, x, (cache.k, cache.v))
    _note_moe(moe_out, ys[2:])
    if slots is None:
        return x, ys[:2]
    return x, (_land_rows(cache.k, ys[0], slots, idx), _land_rows(cache.v, ys[1], slots, idx))


def _appended_length(cache: KVCache, cursors, n_new, slots):
    """The lengths after an append: the lanes' own, or with `slots` the whole
    stack's with the lanes' slots advanced (a padding lane's slot is out of
    range and dropped)."""
    if slots is None:
        return cursors + n_new
    return cache.length.at[slots].set(cursors + n_new, mode="drop")


def _append_forward_mixed(params, cfg, tokens, cache, cursors, positions, live, *, aids, moe_out):
    """_append_forward for a mixed stack. `cache.k` / `cache.v` are the two
    kinds' gathered views, (full [L_full, b, capacity, hkv, hd], window
    [L_window, b, ring, hkv, hd]): the full layers' rows at their absolute
    positions, the window layers' as a ROLLING ring of `ring` rows (row =
    position mod ring, kvcache.paged.gather_ring: the last `ring` positions
    below the cursor are all a window layer can read). A layer indexes its
    kind's view, writes the chunk's rows into that copy and attends
    (chunk_prefill_attention: dense with no window, or the ring's positional
    masks with the window). What comes back beside the hidden states is NOT
    the views but the chunk's own rows, [L, b, c, hkv, hd] for every layer
    of the stack: the caller writes them through each kind's block table."""
    rings = (0, cache.k[1].shape[2])  # full: position-indexed; window: a ring
    idx = tuple(
        jnp.where(live, jnp.mod(positions, ring) if ring else positions, view.shape[2])
        for ring, view in zip(rings, cache.k)
    )
    mm = qmm_a8
    write = jax.vmap(lambda cb, ub, ib: cb.at[ib].set(ub))

    def kind(j, window):
        def attend(at, q, k_new, v_new):
            kc = jax.lax.dynamic_index_in_dim(cache.k[j], at, 0, keepdims=False)
            vc = jax.lax.dynamic_index_in_dim(cache.v[j], at, 0, keepdims=False)
            with jax.named_scope("layer/kv_write"):
                kc = write(kc, k_new.astype(kc.dtype), idx[j])
                vc = write(vc, v_new.astype(vc.dtype), idx[j])
            return chunk_prefill_attention(
                q, kc, vc, cursors, logit_cap=cfg.attn_logit_cap,
                window=window, ring=rings[j],
            )

        return attend

    x = _embed_tokens(params, cfg, tokens)

    def layer(x, lp, _rest):
        def attend(q, k_new, v_new):
            attn = _by_kind(
                cfg, lp[LAYER_KEY], kind(1, cfg.window), kind(0, 0), q, k_new, v_new
            )
            return attn, (k_new.astype(cache.k[0].dtype), v_new.astype(cache.v[0].dtype))

        x, rows = _attn_block(cfg, x, lp, positions, mm, aids, attend)
        x, stats = _mlp_residual(cfg, x, lp, mm, aids)
        return x, rows + stats

    x, ys = _layer_scan(params["layers"], layer, x, (), index=True)
    _note_moe(moe_out, ys[2:])
    return x, ys[:2]


@jax.named_scope("prefill_rows")
def prefill_append(
    params: dict,
    cfg: TransformerConfig,
    tokens: jnp.ndarray,  # [b, c] — one prefill chunk per sequence
    cache: KVCache,  # [L, b, capacity, hkv, hd] slot rows (gathered)
    cursors: jnp.ndarray,  # [b] int32 — prompt tokens already resident
    n_new: jnp.ndarray,  # [b] int32 — valid tokens in this chunk (<= c)
    *,
    ring: int = 0,  # >0: cache is a rolling ring of this capacity
    aids: jnp.ndarray | None = None,  # [b] int32 per-row adapter ids (LoRA)
    mesh=None,  # TP mesh (see _append_forward)
    moe_out: list | None = None,  # a routed model appends its moe stats (_note_moe)
    slots: jnp.ndarray | None = None,  # [b] int32: `cache` is the whole contiguous stack (see _append_forward)
) -> tuple[jnp.ndarray, KVCache]:
    """Append one prefill chunk into an existing per-slot KV cache.

    The chunked-prefill half of the serving engine's token-budget step
    (gofr_tpu.llm): instead of prefilling a whole prompt in one
    bucket-padded wave, prompts advance `n_new` tokens per step through a
    fixed [b, c] chunk shape. Each layer writes the chunk's K/V rows at
    the per-sequence cursor (dense: row index = absolute position; ring:
    position mod capacity) via a masked scatter — indices for i >= n_new
    are pushed out of bounds and DROPPED, so padding lanes never write —
    then attends with ops.chunk_prefill_attention (all resident keys +
    the chunk's causal triangle). Token-equality with the monolithic
    prefill path holds because every (query, key) pair sees exactly the
    same dot products and mask set, only batched differently.

    Unlike decode_chunk there is no per-step ring buffer: the whole chunk
    is one forward pass (c token rows, MXU-bound like prefill), so the
    scatter amortizes over c tokens. The paged pool's gathered view comes
    back restacked by the layer scan, which costs what its gather already
    paid; the contiguous layouts pass ``slots`` and the engine's whole
    stack, and get it back with the chunk's rows written where it lies
    (_append_forward: nothing of a ring's size is gathered, restacked or
    written back) and the lanes' slots' lengths advanced.

    Returns (last-valid-token logits [b, vocab] f32, updated cache with
    length = cursors + n_new). Rows with n_new == 0 return garbage logits
    (callers only read logits for rows whose prompt just completed).
    For a mixed stack `cache.k` / `cache.v` are the two kinds' views and
    what comes back in their place is the chunk's own rows, every layer's
    (_append_forward_mixed): the caller writes them through each kind's table.
    """
    b, c = tokens.shape
    x, (ks, vs) = _append_forward(
        params, cfg, tokens, cache, cursors, n_new, ring=ring, aids=aids,
        mesh=mesh, moe_out=moe_out, slots=slots,
    )
    last = jnp.clip(n_new - 1, 0, c - 1)
    x_last = jnp.take_along_axis(x, last[:, None, None].astype(jnp.int32), axis=1)
    with jax.named_scope("unembed_sample"):  # the caller samples from these
        logits = _unembed_last(params, cfg, x_last)  # [b, vocab] f32
    new_cache = KVCache(k=ks, v=vs, length=_appended_length(cache, cursors, n_new, slots))
    return logits, new_cache


def verify_chunk(
    params: dict,
    cfg: TransformerConfig,
    tokens: jnp.ndarray,  # [b, c] — [last accepted token | draft tokens]
    cache: KVCache,  # [L, b, capacity, hkv, hd] slot rows (gathered)
    cursors: jnp.ndarray,  # [b] int32 — tokens already resident
    n_new: jnp.ndarray,  # [b] int32 — valid tokens (1 + drafts; <= c)
    *,
    ring: int = 0,  # >0: cache is a rolling ring of this capacity
    aids: jnp.ndarray | None = None,  # [b] int32 per-row adapter ids (LoRA)
    mesh=None,  # TP mesh (see _append_forward)
    slots: jnp.ndarray | None = None,  # [b] int32: `cache` is the whole contiguous stack (see _append_forward)
) -> tuple[jnp.ndarray, KVCache]:
    """Score every position of a speculative-decoding draft in ONE
    forward pass (gofr_tpu.spec; docs/advanced-guide/speculative-decoding.md).

    Identical to prefill_append — the same write-then-attend chunk
    append against the slot KV, so position i's logits see exactly the
    keys a sequential decode of tokens[:i+1] would have seen — except
    ALL c positions are unembedded, not just the last: the engine's
    verify program samples each position with its regular top-k
    machinery and accepts the longest prefix agreeing with the draft.

    On rejection the engine rolls the slot cursor back to
    cursor + accepted + 1; rows written here for rejected draft
    positions sit ABOVE the rolled-back cursor and are never attended —
    causally masked on the dense layout, window-masked on the ring
    (capacity >= window + c guarantees their reconstructed positions
    land a full lap behind every later query's window) — until the next
    append overwrites them (ops.chunk_prefill_attention).

    Returns (per-position logits [b, c, vocab] f32, updated cache with
    length = cursors + n_new — callers roll length back to the accepted
    count). Positions >= n_new carry garbage logits the engine ignores.
    """
    x, (ks, vs) = _append_forward(
        params, cfg, tokens, cache, cursors, n_new, ring=ring, aids=aids,
        mesh=mesh, slots=slots,
    )
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _unembed(params, cfg, x)  # [b, c, vocab] f32
    new_cache = KVCache(k=ks, v=vs, length=_appended_length(cache, cursors, n_new, slots))
    return logits, new_cache


def generate(
    params: dict,
    cfg: TransformerConfig,
    prompt: jnp.ndarray,  # [b, s] right-padded
    lengths: jnp.ndarray,  # [b]
    max_new_tokens: int,
    *,
    temperature: float = 0.0,
    rng: jax.Array | None = None,
) -> jnp.ndarray:
    """Greedy (temperature=0) or sampled generation. Fixed-trip lax.scan so
    the whole thing is one compiled program; serving instead drives
    decode_step per token for streaming."""
    b, s = prompt.shape
    last_logits, cache = prefill(params, cfg, prompt, lengths, s + max_new_tokens)
    rng = rng if rng is not None else jax.random.PRNGKey(0)

    def sample(logits, key):
        if temperature > 0.0:
            return jax.random.categorical(key, logits / temperature, axis=-1)
        return jnp.argmax(logits, axis=-1)

    def body(carry, key):
        logits, cache = carry
        tok = sample(logits, key).astype(jnp.int32)
        logits, cache = decode_step(params, cfg, tok, cache)
        return (logits, cache), tok

    keys = jax.random.split(rng, max_new_tokens)
    if max_new_tokens == 1:
        return sample(last_logits, keys[0]).astype(jnp.int32)[:, None]
    # Scan n-1 steps, sample the final token from the last logits directly —
    # avoids paying a forward pass whose logits would be discarded.
    (last_logits, _), toks = jax.lax.scan(body, (last_logits, cache), keys[:-1])
    final = sample(last_logits, keys[-1]).astype(jnp.int32)
    return jnp.concatenate([toks.T, final[:, None]], axis=1)  # [b, max_new_tokens]

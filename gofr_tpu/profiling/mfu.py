"""Analytic model FLOPs, device peaks, and MFU/roofline classification.

MFU (model FLOPs utilization, the PaLM-system-report framing) is the
serving health signal the latency histograms cannot give: *useful* model
FLOPs per second divided by the chip's peak. The analytic side is
computed ONCE per registered model from the architecture — the standard
2·params·tokens matmul count plus the attention correction (4·L·H·d per
token per attended position, QKᵀ and AV) — and the engine combines it
with measured phase wall time per prefill wave / decode chunk.

Roofline classification compares the program's compute time at peak
FLOPs against its memory time at peak HBM bandwidth: decode streams the
whole weight set plus the live KV prefix per step, so it is
memory-bound everywhere that matters; prefill at real batch widths is
compute-bound. A phase whose measured ratio flips side is the first
sign a kernel regressed.

Peaks are tabulated per TPU device kind (bf16 dense MXU numbers, the
convention MFU reports use even when serving int8). Off-TPU there is no
honest peak: the CPU backend uses a nominal 1 TFLOP/s placeholder so
the gauges stay finite and testable — override with the
``TPU_PEAK_FLOPS`` / ``TPU_HBM_BW`` env knobs when you care about the
absolute value.
"""

from __future__ import annotations

import os
from dataclasses import dataclass

__all__ = [
    "ModelCosts",
    "model_costs",
    "decode_flops",
    "prefill_flops",
    "chunk_prefill_flops",
    "spec_verify_flops",
    "device_peak_flops",
    "device_hbm_bandwidth",
    "roofline_ratio",
    "classify_bound",
]

# bf16 dense peak FLOP/s and HBM bandwidth (B/s) by device-kind substring.
# v5e numbers match bench.py's V5E_PEAK_BF16 / V5E_HBM_BW constants.
_TPU_PEAKS: tuple[tuple[str, float, float], ...] = (
    ("v5 lite", 197e12, 8.2e11),
    ("v5e", 197e12, 8.2e11),
    ("v5p", 459e12, 2.765e12),
    ("v6 lite", 918e12, 1.64e12),
    ("v6e", 918e12, 1.64e12),
    ("v4", 275e12, 1.2e12),
    ("v3", 123e12, 9.0e11),
    ("v2", 45e12, 7.0e11),
)

# Off-TPU placeholder peak: keeps MFU/roofline math finite on the CPU
# test backend without pretending to know the host's real roofline.
_FALLBACK_PEAK_FLOPS = 1e12
_FALLBACK_HBM_BW = 1e11


def _tpu_peaks(platform: str, device_kind: str) -> tuple[float, float] | None:
    """(peak FLOP/s, HBM B/s) of a TPU device kind, None off-TPU. A TPU
    kind that is not in the table is an error, not a default: utilization
    against an invented peak reads as a measurement."""
    kind = (device_kind or "").lower()
    if platform != "tpu" and "tpu" not in kind:
        return None
    for sub, peak, bw in _TPU_PEAKS:
        if sub in kind:
            return peak, bw
    raise ValueError(
        f"TPU device kind {device_kind!r} is not in the peaks table "
        "(gofr_tpu.profiling.mfu._TPU_PEAKS); add it with its source"
    )


def _env_float(name: str) -> float | None:
    try:
        return float(os.environ.get(name) or "")
    except ValueError:
        return None


def device_peak_flops(platform: str = "", device_kind: str = "") -> float:
    """Peak dense FLOP/s per chip (bf16 convention). TPU_PEAK_FLOPS
    overrides; off-TPU devices take the nominal placeholder."""
    env = _env_float("TPU_PEAK_FLOPS")
    if env is not None:
        return env
    peaks = _tpu_peaks(platform, device_kind)
    return peaks[0] if peaks else _FALLBACK_PEAK_FLOPS


def device_hbm_bandwidth(platform: str = "", device_kind: str = "") -> float:
    """Peak HBM bandwidth per chip in B/s (TPU_HBM_BW overrides)."""
    env = _env_float("TPU_HBM_BW")
    if env is not None:
        return env
    peaks = _tpu_peaks(platform, device_kind)
    return peaks[1] if peaks else _FALLBACK_HBM_BW


@dataclass(frozen=True)
class ModelCosts:
    """Per-model analytic constants, computed once at engine registration.

    ``matmul_flops_per_token`` is the classic 2·params count over the
    weight matmuls a decoded token touches (layer stack + the unembed
    projection; the embedding *lookup* is a gather, not a matmul).
    ``attn_flops_per_token_per_ctx`` is the attention correction per
    attended position: QKᵀ and AV are each 2·H·d FLOPs per (token,
    position) pair per layer."""

    params: int  # total parameter count (embed counted once when tied)
    layer_params: int  # weight params across the layer stack
    embed_params: int  # vocab x d_model (the unembed matmul's matrix)
    matmul_flops_per_token: int
    attn_flops_per_token_per_ctx: int
    kv_bytes_per_ctx_token: int  # bytes of K+V a step reads per attended position
    params_bytes: int  # resident weight bytes (int8 when quantized)
    # the one window EVERY layer has, 0 where any layer reads all its context
    # (what a step record's decode context is capped by)
    sliding_window: int
    # the keys each layer attends, 0 = all (TransformerConfig.windows): what
    # read_ctx and attended_below cap attention work by, layer by layer
    layer_windows: tuple = ()


def read_ctx(costs: ModelCosts, ctx: int) -> float:
    """Positions a token at context `ctx` attends, a layer on average: what
    `attn_flops_per_token_per_ctx` and `kv_bytes_per_ctx_token` (both summed
    over ALL layers) are multiplied by. min(ctx, window) where every layer
    has the one window; in a mixed stack the window layers' capped share and
    the full layers' whole context."""
    ws = costs.layer_windows or (costs.sliding_window,)
    return sum(min(ctx, w) if w else ctx for w in ws) / len(ws)


def attended_below(costs: ModelCosts, p: int) -> float:
    """Sum over positions 0..p-1 of the keys a token there attends (itself
    included), a layer on average, exact at the window's edge."""

    def one(w: int) -> float:
        if not w or p <= w:
            return p * (p + 1) / 2  # the full causal triangle
        # the first w tokens attend causally, every later one exactly w
        return w * (w + 1) / 2 + (p - w) * w

    ws = costs.layer_windows or (costs.sliding_window,)
    return sum(one(w) for w in ws) / len(ws)


def model_costs(cfg, *, quantized: bool = False) -> ModelCosts:
    """Architecture-derived cost constants for a TransformerConfig.

    Matches the parameter accounting bench.py's raw probes use (attention
    projections with GQA, the 3-matrix gated MLP, one vocab x d embed
    matrix) so the two never disagree about what "2·params" means."""
    d = cfg.d_model
    if getattr(cfg, "latent", False):
        # absorbed latent attention: every head meets one row [c_kv | k_rope]
        C, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
        attn_params = (
            d * cfg.q_lora_rank
            + cfg.q_lora_rank * cfg.n_heads * (cfg.qk_nope_head_dim + dr)
            + d * (C + dr)
            + C * cfg.n_heads * (cfg.qk_nope_head_dim + cfg.v_head_dim)
            + cfg.n_heads * cfg.v_head_dim * d
        )
        attn_per_ctx = 2 * cfg.n_heads * (2 * C + dr)
        kv_values = C + dr
        if getattr(cfg, "sparse", False):  # the lightning indexer's projections and its key
            ih, di = cfg.index_heads, cfg.index_head_dim
            attn_params += cfg.q_lora_rank * ih * di + d * (di + ih)
            kv_values += di
    else:
        attn_params = (
            d * (cfg.n_heads + 2 * cfg.n_kv_heads) * cfg.head_dim  # qkv
            + cfg.n_heads * cfg.head_dim * d  # attention out
        )
        attn_per_ctx = 4 * cfg.n_heads * cfg.head_dim
        kv_values = 2 * cfg.n_kv_heads * cfg.head_dim
    dense_mlp = 3 * d * cfg.d_ff  # gate/up/down
    n_experts = int(getattr(cfg, "n_experts", 0) or 0)
    if n_experts:
        # a token multiplies its top-k experts and the shared ones; every
        # expert is resident
        fe = int(getattr(cfg, "moe_d_ff", 0) or cfg.d_ff)
        shared = int(getattr(cfg, "n_shared_experts", 0) or 0)
        n_dense = cfg.n_dense_layers if len(cfg.group_sizes) > 1 else 0
        n_moe = cfg.n_layers - n_dense
        # (a program that holds a share of the experts multiplies that share
        # of a token's top-k choices on average, and keeps only its own)
        held = int(getattr(cfg, "held_experts", n_experts) or n_experts)
        active = n_dense * dense_mlp + n_moe * int(
            3 * d * fe * (cfg.moe_top_k * held / n_experts + shared) + d * n_experts
        )
        resident = n_dense * dense_mlp + n_moe * (
            3 * d * fe * (held + shared) + d * n_experts
        )
    else:
        active = resident = dense_mlp * cfg.n_layers
    # the layers that attend keys: all of them, or beside linear-attention
    # layers (models.hybrid) the full ones, each linear layer its mixer's
    # projections (q, k, v, the gate z, beta, alpha, out) and no cache rows
    n_attn, mixers = cfg.n_layers, attn_params * cfg.n_layers
    if getattr(cfg, "linear", False):
        from ..models.hybrid import mixer_kinds

        n_attn = len(mixer_kinds(cfg)[0])
        H, dk, dv = cfg.linear_heads, cfg.linear_key_dim, cfg.linear_value_dim
        mixers = attn_params * n_attn + (cfg.n_layers - n_attn) * (d * H * (2 * dk + 2 * dv + 2) + H * dv * d)
    layer_params = mixers + active
    embed_params = cfg.vocab_size * cfg.d_model
    itemsize = 1 if quantized else _dtype_itemsize(cfg.dtype)
    kv_itemsize = _dtype_itemsize(cfg.dtype)  # KV cache stays cfg.dtype
    return ModelCosts(
        params=mixers + resident + embed_params,
        layer_params=layer_params,
        embed_params=embed_params,
        matmul_flops_per_token=2 * (layer_params + embed_params),
        attn_flops_per_token_per_ctx=n_attn * attn_per_ctx,
        kv_bytes_per_ctx_token=n_attn * kv_values * kv_itemsize,
        params_bytes=(mixers + resident + embed_params) * itemsize,
        sliding_window=int(getattr(cfg, "sliding_window", 0) or 0),
        layer_windows=tuple(
            getattr(cfg, "windows", ()) if getattr(cfg, "mixed", False) else ()
        ),
    )


def _dtype_itemsize(dtype) -> int:
    try:
        import numpy as np

        return int(np.dtype(dtype).itemsize)
    except Exception:  # noqa: BLE001 — bf16 has no numpy dtype pre-ml_dtypes
        name = str(getattr(dtype, "__name__", dtype))
        return 2 if "16" in name else 4


def decode_flops(costs: ModelCosts, tokens: int, ctx_total: int) -> float:
    """FLOPs for `tokens` decoded tokens attending over `ctx_total`
    summed context positions (already window-capped by the caller)."""
    return (
        tokens * costs.matmul_flops_per_token
        + costs.attn_flops_per_token_per_ctx * ctx_total
    )


def prefill_flops(costs: ModelCosts, seq_lens: list[int]) -> float:
    """FLOPs for one prefill wave over the given actual prompt lengths.
    Useful-work convention: padding rows and pad tail positions count
    zero, so MFU reads as useful model FLOPs per peak — padding waste
    shows up as LOW utilization rather than being flattered away. The
    unembed matmul runs once per sequence (last position only) and
    causal attention attends ~s/2 positions per token (window-capped)."""
    total = 0.0
    for s in seq_lens:
        attended = attended_below(costs, s)
        total += (
            2 * s * costs.layer_params
            + 2 * costs.embed_params
            + costs.attn_flops_per_token_per_ctx * attended
        )
    return total


def chunk_prefill_flops(costs: ModelCosts, spans: list[tuple[int, int]]) -> float:
    """FLOPs for one chunked-prefill step over `spans` of (cursor, n_new):
    n_new tokens appended at absolute positions [cursor, cursor + n_new).
    Same useful-work convention as prefill_flops (padding lanes count
    zero), but attention is position-exact — token at position p attends
    min(p + 1, window) keys — and the unembed matmul bills once per span
    (the step op computes last-token logits every chunk, which is the
    chunked path's extra cost over one-shot prefill)."""
    total = 0.0
    for cursor, n in spans:
        if n <= 0:
            continue
        attended = attended_below(costs, cursor + n) - attended_below(costs, cursor)
        total += (
            2 * n * costs.layer_params
            + 2 * costs.embed_params
            + costs.attn_flops_per_token_per_ctx * attended
        )
    return total


def spec_verify_flops(costs: ModelCosts, spans: list[tuple[int, int]]) -> float:
    """USEFUL FLOPs for one speculative-decoding verify step over `spans`
    of (cursor, n_emitted): the tokens the step actually produced —
    accepted draft tokens plus the bonus token per lane.

    The useful-work convention, applied to speculation: a verify
    forward pass computes draft+1 positions per lane but only
    n_emitted of them advanced the stream, so VERIFIED-BUT-REJECTED
    positions bill ZERO here — exactly like padding rows in
    prefill_flops. MFU (useful FLOPs / wall / peak) then reads LOW when
    acceptance is poor instead of being flattered by throwaway compute,
    which is the honest signal: a spec engine at 0% acceptance burns
    the wall of a (draft+1)-wide pass for one token of progress.
    Per-token accounting matches decode_flops (full matmul stack +
    unembed per emitted token — every emitted token's position WAS
    sampled from its own unembed) with position-exact attention per
    accepted position, the chunk_prefill_flops span convention."""
    total = 0.0
    for cursor, n in spans:
        if n <= 0:
            continue
        attended = attended_below(costs, cursor + n) - attended_below(costs, cursor)
        total += (
            n * costs.matmul_flops_per_token
            + costs.attn_flops_per_token_per_ctx * attended
        )
    return total


def roofline_ratio(flops: float, bytes_moved: float, peak_flops: float, hbm_bw: float) -> float:
    """compute_time / memory_time for one program execution: > 1 means
    the roofline predicts compute-bound, < 1 memory(HBM)-bound."""
    if bytes_moved <= 0 or peak_flops <= 0 or hbm_bw <= 0:
        return 0.0
    return (flops / peak_flops) / (bytes_moved / hbm_bw)


def classify_bound(ratio: float) -> str:
    if ratio <= 0:
        return "unknown"
    return "compute" if ratio >= 1.0 else "memory"

"""Mixture-of-Experts FFN with expert parallelism (EP).

The reference framework has no ML execution (SURVEY §2.9); this module
exists for the parallelism inventory's EP axis: experts shard over an
`expert` mesh axis and GSPMD turns the dispatch/combine einsums into the
all-to-all + local-FFN pattern — no hand-written collectives, same recipe
as the TP/DP layers (annotate shardings, let XLA partition).

Design — the GShard/Switch dense-dispatch formulation, which is the
TPU-native one (static shapes, MXU-shaped einsums, no ragged gathers):

- Router: logits = x @ w_router, softmax in f32, top-k (k small, over the
  tiny E axis — cheap `lax.top_k`).
- Capacity: each expert processes at most C = ceil(T/E · capacity_factor
  · k) tokens per batch; overflow tokens are dropped for that expert
  (their combine weight is 0) — deterministic, shape-static.
- Dispatch/combine: one-hot [T, E, C] tensors; expert inputs are
  `einsum('tec,td->ecd')`, experts run as a batched (vmapped over E) FFN,
  outputs return via `einsum('tec,ecd->td')` scaled by the gate probs.
- Aux load-balancing loss (Switch-style): E · Σ_e fraction_e · prob_e,
  pushing the router toward uniform expert utilization.

With `x` data-sharded over "data" and experts weight-sharded over
"expert", XLA lowers dispatch to a reduce-scatter/all-to-all onto the
owning expert shard and combine to the reverse — exactly the manual EP
wiring, derived from annotations.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from ..ops import apply_rope, multi_head_attention, rms_norm

__all__ = [
    "routed_ffn",
    "MoEConfig",
    "moe_init",
    "moe_ffn",
    "moe_transformer_forward",
    "moe_lm_loss",
    "moe_param_specs",
]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    vocab_size: int = 512
    d_model: int = 64
    n_layers: int = 2
    n_heads: int = 4
    head_dim: int = 16
    d_ff: int = 128  # per-expert hidden
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-6
    aux_loss_weight: float = 1e-2
    dtype: object = jnp.float32

    @staticmethod
    def tiny(n_experts: int = 8) -> "MoEConfig":
        return MoEConfig(n_experts=n_experts)


def moe_init(rng: jax.Array, cfg: MoEConfig) -> dict:
    d, hd, hq, ff, E, L = (
        cfg.d_model, cfg.head_dim, cfg.n_heads, cfg.d_ff, cfg.n_experts,
        cfg.n_layers,
    )
    keys = jax.random.split(rng, 8)

    def w(key, shape, fan_in):
        return (
            jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)
        ).astype(cfg.dtype)

    return {
        "embed": w(keys[0], (cfg.vocab_size, d), d),
        "final_norm": jnp.zeros((d,), cfg.dtype),
        "layers": {
            "attn_norm": jnp.zeros((L, d), cfg.dtype),
            "wqkv": w(keys[1], (L, d, 3 * hq * hd), d),
            "wo": w(keys[2], (L, hq * hd, d), hq * hd),
            "mlp_norm": jnp.zeros((L, d), cfg.dtype),
            "w_router": w(keys[3], (L, d, E), d),
            # experts batched on a leading E axis — the EP shard axis
            "w_gate": w(keys[4], (L, E, d, ff), d),
            "w_up": w(keys[5], (L, E, d, ff), d),
            "w_down": w(keys[6], (L, E, ff, d), ff),
        },
    }


_MOE_ACTS = {"gelu": jax.nn.gelu, "silu": jax.nn.silu}


def _deq(w):
    """int8-quantized expert/router weights dequantize into f32 before the
    dispatch einsums — QTensor can't ride einsum/vmap directly, and the E
    axis is tiny so the dequant cost is noise next to the expert matmuls."""
    from .quant import QTensor

    if isinstance(w, QTensor):
        return w.q.astype(jnp.float32) * w.s.astype(jnp.float32)
    return w


def group_limited(choose: jnp.ndarray, groups: int, keep: int) -> jnp.ndarray:
    """Group-limited choice (DeepSeek-V3's noaux_tc): the experts [T, E] in
    `groups` equal groups, each scored by the sum of its two best `choose`
    values; the experts of all but the `keep` best groups leave the choice
    (their `choose` becomes -inf). A group of one expert scores by it alone."""
    T, E = choose.shape
    by_group = choose.reshape(T, groups, E // groups)
    best = jax.lax.top_k(by_group, min(2, E // groups))[0].sum(axis=-1)  # [T, groups]
    _, kept = jax.lax.top_k(best, keep)
    eligible = jnp.zeros((T, groups), jnp.bool_).at[jnp.arange(T)[:, None], kept].set(True)
    return jnp.where(eligible[:, :, None], by_group, -jnp.inf).reshape(T, E)


def route(cfg, h: jnp.ndarray, lp: dict) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The router of one layer over token rows h [T, d], in float32 whatever
    the activations' dtype: -> (experts [T, k] int32, weights [T, k] f32).
    What differs between checkpoints is configuration: the score function
    (softmax | sigmoid), a correction bias that moves the CHOICE and never the
    weight (`router_bias` leaf), normalising the chosen weights, a scale."""
    # "highest": the TPU's default float32 matmul is one bfloat16 pass, and a
    # near tie decided in bfloat16 picks another expert than the weights say
    logits = jnp.dot(
        h.astype(jnp.float32), _deq(lp["w_router"]).astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    if cfg.moe_score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif cfg.moe_score == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"unknown moe_score {cfg.moe_score!r}; expected softmax or sigmoid")
    choose = scores
    if "router_bias" in lp:
        choose = scores + lp["router_bias"].astype(jnp.float32)
    groups = getattr(cfg, "moe_groups", 1)
    if groups > 1:
        choose = group_limited(choose, groups, cfg.moe_topk_groups)
    _, experts = jax.lax.top_k(choose, cfg.moe_top_k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if cfg.moe_norm_topk:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return experts.astype(jnp.int32), weights * cfg.moe_scale


def _row_tile(pairs: int, experts: int, on_tpu: bool) -> int:
    """Rows a tile of the sorted layout holds: whole bf16 sublane tiles on
    the TPU, wider once an expert sees that many rows on average."""
    if not on_tpu:
        return 8
    return 16 if pairs <= 16 * experts else 64


def routed_ffn(cfg, h: jnp.ndarray, lp: dict, mm, *, use_kernel=None, interpret=False):
    """The ONE routed FFN of serving: dropless top-k experts (+ shared ones).

    h [T, d] -> (y [T, d], counts [E] int32: rows each expert was given).
    A program that holds a SHARE of the experts (cfg.moe_held_experts of the
    n_experts, from cfg.moe_first_expert: one chip of an expert-parallel
    deployment) routes over all of them all the same (`route`: the scores,
    the choice and the normalised weights are the whole model's), leaves the
    pairs whose expert is elsewhere out BEFORE the sort, runs the grouped
    matmuls over its own pairs and its own stacks [held, in, out] alone, and
    sums what it computed: its part of the layer's result, the shared
    experts whole (every chip computes those alike). counts is then [held].
    Nothing stands in for the absent chips or their exchange.
    Route (`route`), sort the (token, expert) pairs by expert into a layout
    where each expert's rows start on a tile boundary, three grouped matmuls
    over the expert stacks as they are stored (ops.grouped: int8 stays int8
    in HBM), and gather each token's k rows back with its weights. No pair
    is ever dropped, and a row's arithmetic does not depend on which other
    rows share its tile, so a token's output is its own whatever the batch.
    Shared experts (`ws_gate`/`ws_up`/`ws_down`) are a dense gated FFN over
    every token through `mm` (qmm | qmm_a8)."""
    from ..ops.grouped import LayerOf, grouped_matmul

    T = h.shape[0]
    E, k = cfg.n_experts, cfg.moe_top_k
    act_fn = _MOE_ACTS[cfg.act]
    with jax.named_scope("layer/moe_route"):
        experts, weights = route(cfg, h, lp)
        pairs = T * k
        share = bool(cfg.moe_held_experts) and cfg.moe_held_experts < cfg.n_experts
        if share:
            # an expert held elsewhere becomes E (one past this program's
            # own): it sorts behind every held expert and is given no row
            E = cfg.moe_held_experts
            own = experts - cfg.moe_first_expert
            experts = jnp.where((own >= 0) & (own < E), own, E)
        tm = _row_tile(pairs, E, interpret or jax.default_backend() == "tpu")
        flat_e = experts.reshape(pairs)  # pair p = token p // k, choice p % k
        counts = jnp.zeros((E,), jnp.int32).at[flat_e].add(1)  # (expert E: dropped)
        padded = -(-counts // tm) * tm
        ends_pad = jnp.cumsum(padded)
        starts, starts_pad = jnp.cumsum(counts) - counts, ends_pad - padded
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        dest_sorted = starts_pad[sorted_e] + jnp.arange(pairs, dtype=jnp.int32) - starts[sorted_e]
        n_tiles = -(-pairs // tm) + min(E, pairs)  # every non-empty expert wastes under a tile
        rows = n_tiles * tm
        if share:  # a pair computed elsewhere has no row here: one past the last
            dest_sorted = jnp.where(sorted_e < E, dest_sorted, rows)
        dest = jnp.zeros((pairs,), jnp.int32).at[order].set(dest_sorted)  # pair -> row
        src = jnp.full((rows,), T, jnp.int32).at[dest].set(
            jnp.arange(pairs, dtype=jnp.int32) // k
        )  # row -> token; T (out of range) = no token
        used_tiles = ends_pad[-1] // tm
        last = jnp.max(jnp.where(counts > 0, jnp.arange(E, dtype=jnp.int32), 0))
        tile_expert = jnp.minimum(
            jnp.searchsorted(ends_pad, jnp.arange(n_tiles, dtype=jnp.int32) * tm, side="right"),
            last,
        ).astype(jnp.int32)  # spare tiles name the last live expert: no fetch of their own
    with jax.named_scope("layer/moe_experts"):
        x = jnp.take(h, src, axis=0, mode="fill", fill_value=0)  # row T: no token, zeros
        gmm = functools.partial(
            grouped_matmul, tile_expert=tile_expert, used_tiles=used_tiles,
            padded_counts=padded, tile_rows=tm, use_kernel=use_kernel, interpret=interpret,
        )
        w_gate, w_up, w_down = (LayerOf.of(lp[n]) for n in ("w_gate", "w_up", "w_down"))
        a = act_fn(gmm(x, w_gate)) * gmm(x, w_up)
        out = gmm(a, w_down)  # [rows, d]
        if share:  # rows of pairs computed elsewhere read as zeros
            picked = jnp.take(out, dest.reshape(T, k), axis=0, mode="fill", fill_value=0)
        else:
            picked = jnp.take(out, dest.reshape(T, k), axis=0)
        picked = picked.astype(jnp.float32)  # [T, k, d]
        y = jnp.sum(picked * weights[..., None], axis=1)
    if "ws_gate" in lp:
        with jax.named_scope("layer/moe_shared"):
            y = y + mm(act_fn(mm(h, lp["ws_gate"])) * mm(h, lp["ws_up"]), lp["ws_down"]).astype(
                jnp.float32
            )
    return y.astype(h.dtype), counts


def moe_ffn(
    x: jnp.ndarray,  # [T, d] token-major
    w_router: jnp.ndarray,  # [d, E]
    w_gate: jnp.ndarray,  # [E, d, ff]
    w_up: jnp.ndarray,
    w_down: jnp.ndarray,  # [E, ff, d]
    cfg: MoEConfig | None = None,
    *,
    n_experts: int | None = None,
    top_k: int | None = None,
    capacity_factor: float | None = None,
    act: str = "gelu",
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (y [T, d], aux_loss scalar).

    Routing hyperparameters come either from explicit kwargs (the serving
    path — models.transformer._mlp_block dispatches here when a layer
    carries a router) or from a legacy MoEConfig positional (the in-file
    training-shaped callers). Weights may be int8 QTensors (see _deq)."""
    if cfg is not None:
        n_experts = cfg.n_experts if n_experts is None else n_experts
        top_k = cfg.top_k if top_k is None else top_k
        if capacity_factor is None:
            capacity_factor = cfg.capacity_factor
    E, k = int(n_experts), int(top_k)
    cf = 1.25 if capacity_factor is None else float(capacity_factor)
    act_fn = _MOE_ACTS[act]
    w_router, w_gate, w_up, w_down = (
        _deq(w_router), _deq(w_gate), _deq(w_up), _deq(w_down),
    )
    T, d = x.shape
    C = max(1, math.ceil(T / E * cf * k))

    logits = (x.astype(jnp.float32)) @ w_router.astype(jnp.float32)  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)  # [T, k]

    # position of each (token, slot) inside its expert's capacity buffer:
    # flatten slots k-major so earlier tokens (and a token's higher-prob
    # slot) claim capacity first — deterministic overflow dropping
    onehot = jax.nn.one_hot(top_e, E, dtype=jnp.int32)  # [T, k, E]
    flat = onehot.transpose(1, 0, 2).reshape(k * T, E)  # slot-major [kT, E]
    pos_flat = jnp.cumsum(flat, axis=0) - 1  # [kT, E] position per expert
    pos = pos_flat.reshape(k, T, E).transpose(1, 0, 2)  # [T, k, E]
    slot_pos = jnp.sum(pos * onehot, axis=-1)  # [T, k]
    keep = slot_pos < C  # overflow -> dropped

    # dispatch [T, E, C] one-hot; combine carries the gate probability
    disp = (
        jax.nn.one_hot(top_e, E, dtype=jnp.float32)[..., None]
        * jax.nn.one_hot(jnp.where(keep, slot_pos, C), C + 1, dtype=jnp.float32)[
            :, :, None, :C
        ]
    )  # [T, k, E, C]
    combine = jnp.sum(disp * top_p[..., None, None].astype(jnp.float32), axis=1)
    dispatch = jnp.sum(disp, axis=1)  # [T, E, C]

    xin = jnp.einsum("tec,td->ecd", dispatch, x.astype(jnp.float32))  # [E, C, d]

    def expert(w_g, w_u, w_d, h):
        a = act_fn(h @ w_g.astype(jnp.float32)) * (h @ w_u.astype(jnp.float32))
        return a @ w_d.astype(jnp.float32)

    yout = jax.vmap(expert)(w_gate, w_up, w_down, xin)  # [E, C, d]
    y = jnp.einsum("tec,ecd->td", combine, yout).astype(x.dtype)

    # Switch aux loss: E * sum_e (fraction routed to e) * (mean prob of e)
    frac = jnp.sum(jax.nn.one_hot(top_e[:, 0], E, dtype=jnp.float32), axis=0) / T
    aux = E * jnp.sum(frac * jnp.mean(probs, axis=0))
    return y, aux


def moe_transformer_forward(
    params: dict, cfg: MoEConfig, tokens: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """[b, s] -> (logits [b, s, vocab] f32, total aux loss). Causal MHA +
    MoE FFN per layer; layers scanned like models.transformer."""
    b, s = tokens.shape
    d, hq, hd = cfg.d_model, cfg.n_heads, cfg.head_dim
    x = params["embed"][tokens].astype(cfg.dtype)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    def layer(carry, lp):
        x, aux = carry
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        qkv = (h @ lp["wqkv"]).reshape(b, s, 3, hq, hd)
        q, k_, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        q = apply_rope(q, positions, cfg.rope_theta)
        k_ = apply_rope(k_, positions, cfg.rope_theta)
        attn = multi_head_attention(q, k_, v, causal=True)
        x = x + (attn.reshape(b, s, hq * hd) @ lp["wo"]).astype(x.dtype)
        h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        y, a = moe_ffn(
            h.reshape(b * s, d), lp["w_router"], lp["w_gate"], lp["w_up"],
            lp["w_down"], cfg,
        )
        return (x + y.reshape(b, s, d), aux + a), None

    (x, aux), _ = jax.lax.scan(layer, (x, jnp.float32(0.0)), params["layers"])
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = (x @ params["embed"].T.astype(cfg.dtype)).astype(jnp.float32)
    return logits, aux


def moe_lm_loss(
    params: dict, cfg: MoEConfig, tokens: jnp.ndarray, mask: jnp.ndarray
) -> jnp.ndarray:
    logits, aux = moe_transformer_forward(params, cfg, tokens)
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    w = mask[:, 1:].astype(jnp.float32)
    ce = jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)
    return ce + cfg.aux_loss_weight * aux


def moe_param_specs(cfg: MoEConfig, mesh, *, expert_axis: str = "expert") -> dict:
    """PartitionSpec pytree for EP: expert-batched weights sharded on their
    E axis, everything else replicated. Compose with a "data" axis on the
    batch for DP x EP."""
    from jax.sharding import PartitionSpec as P

    e = expert_axis if mesh.shape.get(expert_axis, 1) > 1 else None
    return {
        "embed": P(None, None),
        "final_norm": P(None),
        "layers": {
            "attn_norm": P(None, None),
            "wqkv": P(None, None, None),
            "wo": P(None, None, None),
            "mlp_norm": P(None, None),
            "w_router": P(None, None, None),
            "w_gate": P(None, e, None, None),
            "w_up": P(None, e, None, None),
            "w_down": P(None, e, None, None),
        },
    }

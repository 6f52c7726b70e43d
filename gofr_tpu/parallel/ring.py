"""Ring attention: causal attention with the sequence dim sharded over a
mesh axis, K/V rotating around the ring via ppermute while every device
accumulates its queries' online softmax. Memory per device is O(seq/N) and
the K/V transfer overlaps with compute in XLA's pipeline — the TPU-native
answer to long-context, replacing nothing in the reference (which has no
sequence execution, SURVEY.md §5 "Long-context: absent").

Algorithm (blockwise/ring attention, Liu et al. style): each of the N
sequence shards holds q,k,v chunks of the globally-ordered sequence; step t
lets shard i attend to the chunk originally owned by shard (i - t) mod N.
Causality at chunk granularity: skip chunks from later positions, apply the
triangular mask only on the diagonal (t == 0).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.attention import NEG_INF


def _chunk_attn(q, k, v, scale, mask):
    """q [b,sq,h,d] x k/v [b,sk,h,d] -> (scores-exp sum, max, weighted v).
    mask: None (full) or [sq, sk] bool."""
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale, k.astype(jnp.float32))
    if mask is not None:
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)  # [b,h,q,1]
    p = jnp.exp(logits - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhqk,bkhd->bhqd", p, v.astype(jnp.float32))
    return m, l, o


def ring_attention(
    q: jnp.ndarray,  # [b, s, h, d] — s sharded over `axis`
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    mesh: Mesh,
    axis: str = "seq",
    causal: bool = True,
    scale: float | None = None,
    window: int = 0,  # sliding window over GLOBAL positions; 0 = full
) -> jnp.ndarray:
    """Drop-in for multi_head_attention when seq is sharded. GQA: pass K/V
    already expanded to q's head count (ring traffic is the cost anyway).

    window > 0 applies the Mistral band (q_pos - window, q_pos] in global
    coordinates: chunks entirely behind every local query's band are
    skipped at the lax.cond (their rotation still happens — the ring
    schedule is fixed — but their attention math doesn't), and straddling
    chunks get an elementwise band mask. Rows transiently fully-masked in
    a chunk self-correct through the finite-NEG_INF online softmax, the
    same mechanism the flash kernel relies on; the diagonal chunk always
    holds each row's own position, so no row ends fully masked."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    n = mesh.shape[axis]

    def local(qc, kc, vc):
        axis_idx = jax.lax.axis_index(axis)
        b, sq, h, d = qc.shape
        tri = jnp.tril(jnp.ones((sq, sq), bool))

        # pcast-to-varying: accumulators are per-shard values (device-varying
        # over the ring axis), matching branch outputs under the VMA check.
        m_acc = jax.lax.pcast(jnp.full((b, h, sq, 1), NEG_INF, jnp.float32), axis, to="varying")
        l_acc = jax.lax.pcast(jnp.zeros((b, h, sq, 1), jnp.float32), axis, to="varying")
        o_acc = jax.lax.pcast(jnp.zeros((b, h, sq, d), jnp.float32), axis, to="varying")

        # Static unroll over the ring (n = mesh axis size, known at trace
        # time): lets the diagonal mask be chosen statically and skips the
        # pointless final rotation (n-1 ppermutes, not n).
        for t in range(n):
            src_idx = (axis_idx - t) % n  # chunk owner at this rotation
            # Chunk-level causality: attend iff src chunk is not in the future.
            live = src_idx <= axis_idx if causal else jnp.bool_(True)
            if window > 0:
                # chunk dead iff entirely behind every local query's band:
                # its last global position <= first local q position - window
                band_live = (src_idx + 1) * sq - 1 > axis_idx * sq - window
                live = jnp.logical_and(live, band_live)

            def do(carry_in, kc=kc, vc=vc, t=t, src_idx=src_idx):
                m_acc, l_acc, o_acc = carry_in
                # Diagonal chunk (t == 0) needs the triangular mask; earlier
                # chunks are fully visible (the cond already gated future
                # chunks out) unless a band boundary cuts through them.
                if window > 0:
                    qpos = axis_idx * sq + jnp.arange(sq)[:, None]
                    kpos = src_idx * sq + jnp.arange(sq)[None, :]
                    mask = kpos > qpos - window
                    if causal and t == 0:
                        mask = mask & tri
                else:
                    mask = tri if (causal and t == 0) else None
                m_c, l_c, o_c = _chunk_attn(qc, kc, vc, scale, mask)
                m_new = jnp.maximum(m_acc, m_c)
                a_old = jnp.exp(m_acc - m_new)
                a_new = jnp.exp(m_c - m_new)
                return (
                    m_new,
                    l_acc * a_old + l_c * a_new,
                    o_acc * a_old + o_c * a_new,
                )

            m_acc, l_acc, o_acc = jax.lax.cond(
                live, do, lambda c: c, (m_acc, l_acc, o_acc)
            )
            if t < n - 1:
                # Rotate K/V to the next device; the permute rides ICI.
                perm = [(i, (i + 1) % n) for i in range(n)]
                kc = jax.lax.ppermute(kc, axis, perm)
                vc = jax.lax.ppermute(vc, axis, perm)

        l_acc = jnp.where(l_acc == 0.0, 1.0, l_acc)
        out = (o_acc / l_acc).astype(qc.dtype)  # [b,h,sq,d]
        return out.transpose(0, 2, 1, 3)

    spec = P(None, axis, None, None)
    return shard_map(
        local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
    )(q, k, v)


@functools.lru_cache(maxsize=32)
def _ring_prefill_fn(cfg, mesh: Mesh, axis: str, max_cache_len: int):
    """One jitted executable per (cfg, mesh, axis, cache size) — a fresh
    closure per call would miss jax's compile cache and re-trace the whole
    model every prefill."""
    from ..models.transformer import prefill as _prefill

    reps = cfg.n_heads // cfg.n_kv_heads

    window = getattr(cfg, "sliding_window", 0)

    def attn(q, k, v):
        # GQA: expand K/V to q's head count (ring traffic is the cost here
        # and KV is 1/reps of it; see ring_attention docstring)
        if reps > 1:
            k = jnp.repeat(k, reps, axis=2)
            v = jnp.repeat(v, reps, axis=2)
        return ring_attention(
            q, k, v, mesh=mesh, axis=axis, causal=True, window=window
        )

    @jax.jit
    def run(params, tokens, lengths):
        return _prefill(
            params, cfg, tokens, lengths, max_cache_len, prefill_attn=attn
        )

    return run


def ring_prefill(
    params: dict,
    cfg,
    tokens: jnp.ndarray,  # [b, s] right-padded, s sharded over `axis`
    lengths: jnp.ndarray,  # [b]
    *,
    mesh: Mesh,
    axis: str = "seq",
    max_cache_len: int | None = None,
):
    """Long-context sequence-parallel prefill: the FULL transformer forward
    with activations sharded over the sequence axis, attention via
    ring_attention, everything else partitioned by GSPMD from the input
    sharding. Per-device memory is O(s/N) activations + O(s/N) KV — the
    path for prompts whose activations/KV exceed one chip's HBM.

    Returns (last_logits [b, vocab], KVCache) with cache.k/v seq-sharded
    on the cache length axis (reshard/gather to feed single-chip decode,
    or keep sharded for SP decode). max_cache_len defaults to s — pass
    s + decode headroom when the cache will feed decode_step (its
    documented precondition is cache.length < max_len; a headroom-less
    cache from a full-length prompt would silently clamp-overwrite the
    last KV slot).

    s must divide by mesh.shape[axis]. Gemma-2 attn logit soft-capping is
    not supported on the ring path (cap folds into the online softmax
    non-trivially); gemma_2b/llama presets have cap = 0.
    """
    from jax.sharding import NamedSharding

    if getattr(cfg, "attn_logit_cap", 0.0):
        raise NotImplementedError("ring_prefill: attn_logit_cap unsupported")
    n = mesh.shape[axis]
    b, s = tokens.shape
    if s % n != 0:
        raise ValueError(f"seq {s} not divisible by {axis}={n}")

    seq_sharded = NamedSharding(mesh, P(None, axis))
    tokens = jax.device_put(tokens, seq_sharded)
    lengths = jax.device_put(lengths, NamedSharding(mesh, P(None)))
    run = _ring_prefill_fn(cfg, mesh, axis, max_cache_len or s)
    return run(params, tokens, lengths)

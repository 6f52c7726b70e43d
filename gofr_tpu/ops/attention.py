"""Attention: Pallas flash-attention kernel for TPU + XLA reference path.

Layout convention everywhere: [batch, seq, heads, head_dim] at module
boundaries ("BSHD"); the flash kernel internally works per (batch, head)
grid cell. GQA is supported natively — K/V carry n_kv_heads and the kernel's
BlockSpec index_map points each query head at its KV group, so grouped KV is
never materialized at full head count (saves HBM bandwidth, the usual TPU
bottleneck).

The flash kernel is the canonical online-softmax blockwise algorithm: grid
(batch, q_heads, q_blocks, k_blocks) with the k dimension innermost;
running max / normalizer / output accumulator live in VMEM scratch that
persists across the sequential k iterations, finalized on the last k block.
Causal masking skips fully-masked k blocks via pl.when.

No counterpart in the reference repo (a Go web framework, SURVEY.md §2.9);
this implements the TPU north star's compute path (BASELINE.json).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

NEG_INF = -2.3819763e38  # close to bf16 min; avoids nan from (-inf) - (-inf)


# ---------------------------------------------------------------------------
# Reference path (XLA). Used on CPU, for odd shapes, and as the test oracle.
# ---------------------------------------------------------------------------


def mha_reference(
    q: jnp.ndarray,  # [b, sq, hq, d]
    k: jnp.ndarray,  # [b, sk, hkv, d]
    v: jnp.ndarray,  # [b, sk, hkv, d]
    *,
    causal: bool = True,
    scale: float | None = None,
    logit_cap: float = 0.0,
    kv_mask: jnp.ndarray | None = None,  # [b, sk] bool, True = attend
    q_positions: jnp.ndarray | None = None,  # [b, sq] absolute positions
    window: int = 0,  # sliding window: attend to (q_pos - window, q_pos]
) -> jnp.ndarray:
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    group = hq // hkv

    qf = q.astype(jnp.float32) * scale
    # [b, hkv, group, sq, d] x [b, hkv, sk, d] -> [b, hkv, group, sq, sk]
    qg = qf.transpose(0, 2, 1, 3).reshape(b, hkv, group, sq, d)
    kf = k.astype(jnp.float32).transpose(0, 2, 1, 3)  # [b, hkv, sk, d]
    vf = v.astype(jnp.float32).transpose(0, 2, 1, 3)
    logits = jnp.einsum("bkgqd,bksd->bkgqs", qg, kf)
    if logit_cap > 0.0:
        logits = logit_cap * jnp.tanh(logits / logit_cap)

    sk = k.shape[1]
    mask = jnp.ones((b, sq, sk), dtype=bool)
    if causal or window > 0:
        qpos = (
            q_positions
            if q_positions is not None
            else jnp.broadcast_to(jnp.arange(sq), (b, sq))
        )
        kpos = jnp.arange(sk)
        if causal:
            mask = mask & (kpos[None, None, :] <= qpos[:, :, None])
        if window > 0:
            # sliding window (Mistral): keys older than window-1 positions
            # before the query are masked out
            mask = mask & (kpos[None, None, :] > qpos[:, :, None] - window)
    if kv_mask is not None:
        mask = mask & kv_mask[:, None, :]
    logits = jnp.where(mask[:, None, None, :, :], logits, NEG_INF)

    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bksd->bkgqd", probs, vf)
    out = out.reshape(b, hq, sq, d).transpose(0, 2, 1, 3)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas kernels under a tensor-parallel mesh
# ---------------------------------------------------------------------------


def _head_axes(mesh, hq: int, hkv: int, axis: str = "model"):
    """(q, kv) mesh axes a per-head kernel shards its heads over, None =
    replicated — the whole-head rule of parallel.sharding: q when the TP
    degree divides n_heads, kv when it also divides n_kv_heads (or there
    is one kv head for every shard to read). Mosaic kernels have no GSPMD
    partitioning rule (jax refuses to lower one under a multi-device jit),
    so under a mesh each kernel runs inside a shard_map over these axes:
    every chip attends its own heads against its own shard of the KV —
    nothing is gathered, and the per-head math is the single-chip math."""
    tp = mesh.shape.get(axis, 1)
    if tp == 1 or hq % tp:
        return None, None
    if hkv % tp == 0:
        return axis, axis
    return (axis, None) if hkv == 1 else (None, None)


# ---------------------------------------------------------------------------
# Pallas flash attention (TPU prefill path)
# ---------------------------------------------------------------------------


def _flash_kernel(
    off_ref,  # scalar prefetch: [b] int32 per-batch query offsets
    q_ref, k_ref, v_ref, o_ref, m_scratch, l_scratch, acc_scratch,
    *,
    causal: bool,
    scale: float,
    logit_cap: float,
    window: int,
    block_q: int,
    block_k: int,
    num_k_blocks: int,
    offset: bool = False,
):
    # Ref layout: the per-batch query offsets (scalar prefetch, SMEM; read
    # only on the chunk-append prefill path), inputs, the output, then
    # VMEM scratch: running max / denom (lane-replicated) + f32
    # accumulator, persistent across the sequential k iterations.
    qi = pl.program_id(2)
    ki_raw = pl.program_id(3)
    grid_k = pl.num_programs(3)

    @pl.when(ki_raw == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    # Banded grid (causal sliding window): the k grid dim spans only the
    # band-intersecting blocks; remap ki_raw to the ACTUAL k block index
    # ending at this q block's last needed block — the same formula the
    # BlockSpec index_map uses, so compute positions match the DMA'd
    # block. An unclamped index < 0 means this slot aliases block 0's DMA
    # (early q blocks) and must be skipped or block 0 double-counts.
    banded = causal and window > 0 and grid_k < num_k_blocks
    if banded:
        kb_hi = ((qi + 1) * block_q - 1) // block_k
        ki_unclamped = kb_hi - (grid_k - 1) + ki_raw
        ki = jnp.maximum(ki_unclamped, 0)
        in_range = ki_unclamped >= 0
    else:
        ki = ki_raw
        in_range = True

    # Per-batch query offset (chunk-append prefill): query row i sits at
    # absolute position off + i while key block positions stay absolute
    # cache row indices. The offset is a traced value, so block liveness
    # is decided compute-side (pl.when takes dynamic predicates); the
    # banded-grid DMA skip stays disabled on this path (flash_attention
    # never requests both).
    off = off_ref[pl.program_id(0)] if offset else 0

    # Causal: block is live iff some query position >= some key position,
    # i.e. block_q_end >= block_k_start. Sliding window additionally kills
    # blocks entirely BEHIND the band (block_k_end <= block_q_start -
    # window) — with the banded grid those blocks aren't even fetched;
    # without it (non-causal or tiny seq) they are skipped compute-side.
    live = off + (qi + 1) * block_q - 1 >= ki * block_k if causal else True
    if window > 0:
        band_live = (ki + 1) * block_k - 1 > off + qi * block_q - window
        live = jnp.logical_and(live, band_live) if causal else band_live
    live = jnp.logical_and(live, in_range) if banded else live

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # [block_q, d]
        k = k_ref[0, 0].astype(jnp.float32)  # [block_k, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [block_q, block_k]
        if logit_cap > 0.0:
            s = logit_cap * jnp.tanh(s / logit_cap)
        if causal or window > 0:
            qpos = off + qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            kpos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            if causal:
                s = jnp.where(kpos <= qpos, s, NEG_INF)
            if window > 0:
                s = jnp.where(kpos > qpos - window, s, NEG_INF)

        m_prev = m_scratch[:, :1]  # [block_q, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)  # [block_q, block_k]
        alpha = jnp.exp(m_prev - m_new)  # [block_q, 1]
        l_new = alpha * l_scratch[:, :1] + jnp.sum(p, axis=-1, keepdims=True)

        m_scratch[:] = jnp.broadcast_to(m_new, m_scratch.shape)
        l_scratch[:] = jnp.broadcast_to(l_new, l_scratch.shape)
        acc_scratch[:] = acc_scratch[:] * alpha + jax.lax.dot_general(
            p,
            v_ref[0, 0].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki_raw == grid_k - 1)
    def _finalize():
        denom = l_scratch[:, :1]
        denom = jnp.where(denom == 0.0, 1.0, denom)  # fully-masked rows -> 0
        o_ref[0, 0] = (acc_scratch[:] / denom).astype(o_ref.dtype)


def flash_attention(
    q: jnp.ndarray,  # [b, sq, hq, d]
    k: jnp.ndarray,  # [b, sk, hkv, d]
    v: jnp.ndarray,  # [b, sk, hkv, d]
    *,
    causal: bool = True,
    scale: float | None = None,
    logit_cap: float = 0.0,
    window: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    q_offsets: jnp.ndarray | None = None,  # [b] int32 per-batch query offset
    interpret: bool = False,
    mesh=None,  # TP mesh: the kernel runs per head shard (_head_axes)
) -> jnp.ndarray:
    """Blockwise online-softmax attention on the Pallas TPU kernel.

    q_offsets (chunk-append prefill): query row i of batch b sits at
    absolute position q_offsets[b] + i while key positions stay absolute
    cache row indices — a query block attends all prior keys already
    resident in the cache plus its own chunk's causal triangle. Offsets
    are traced values, so block liveness is decided in-kernel and the
    banded-grid DMA skip is disabled on this path (every k block is
    fetched; masked blocks are skipped compute-side)."""
    b, sq, hq, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    if sq % block_q or sk % block_k:
        raise ValueError(f"seq lengths ({sq},{sk}) must divide blocks ({block_q},{block_k})")
    if hq % hkv:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
    if mesh is not None and mesh.size > 1:
        qa, ka = _head_axes(mesh, hq, hkv)
        local = functools.partial(
            flash_attention, causal=causal, scale=scale, logit_cap=logit_cap,
            window=window, block_q=block_q, block_k=block_k, interpret=interpret,
        )
        q_spec, kv_spec = P(None, None, qa, None), P(None, None, ka, None)
        offs = () if q_offsets is None else (q_offsets,)
        return jax.shard_map(
            lambda q, k, v, *off: local(q, k, v, q_offsets=off[0] if off else None),
            mesh=mesh, in_specs=(q_spec, kv_spec, kv_spec, *(P() for _ in offs)),
            out_specs=q_spec, check_vma=False,
        )(q, k, v, *offs)
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    num_k_blocks = sk // block_k
    offset = q_offsets is not None

    # BHSD layout inside the kernel: contiguous [seq, d] slabs per head.
    qt = q.transpose(0, 2, 1, 3)  # [b, hq, sq, d]
    kt = k.transpose(0, 2, 1, 3)  # [b, hkv, sk, d]
    vt = v.transpose(0, 2, 1, 3)

    # Banded grid for causal sliding windows: only the k blocks that can
    # intersect a q block's band are iterated (and hence DMA'd) — the
    # measured difference at 16k/window-1024 is the dead-block K/V copies,
    # not the skipped compute. The exact per-q-block count is periodic in
    # the q-block start mod block_k (plus a ramp while the band clips at
    # 0), so take the true max over one ramp + one period of q blocks —
    # a closed-form bound over-fetches one dead block per q block at the
    # shipped aligned 128/128 config. Dynamic q_offsets make the band
    # data-dependent, so the offset path keeps the full k grid.
    if causal and window > 0 and not offset:
        nqb = sq // block_q
        limit = min(
            nqb, (window - 1) // block_q + math.lcm(block_q, block_k) // block_q + 1
        )
        grid_k = max(
            (qi * block_q + block_q - 1) // block_k
            - max(0, qi * block_q - window + 1) // block_k
            + 1
            for qi in range(limit)
        )
        grid_k = min(grid_k, num_k_blocks)
    else:
        grid_k = num_k_blocks

    def q_index(bi, hi, qi, ki, off):
        return (bi, hi, qi, 0)

    def kv_index(bi, hi, qi, ki, off):
        if grid_k == num_k_blocks:
            return (bi, hi // group, ki, 0)
        kb_hi = ((qi + 1) * block_q - 1) // block_k
        return (bi, hi // group, jnp.maximum(kb_hi - (grid_k - 1) + ki, 0), 0)

    grid = (b, hq, sq // block_q, grid_k)
    kernel = functools.partial(
        _flash_kernel,
        causal=causal,
        scale=scale,
        logit_cap=logit_cap,
        window=window,
        block_q=block_q,
        block_k=block_k,
        num_k_blocks=num_k_blocks,
        offset=offset,
    )
    # The offsets ride as a scalar-prefetch operand (whole [b] vector in
    # SMEM, indexed by the batch grid coordinate): a blocked (1, 1) SMEM
    # spec of a [b, 1] array does not lower on TPU.
    offsets = (
        q_offsets.astype(jnp.int32) if offset else jnp.zeros((b,), jnp.int32)
    )
    out = pl.pallas_call(
        kernel,
        name="flash_prefill",  # what a device trace calls the kernel
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, block_q, d), q_index),
                pl.BlockSpec((1, 1, block_k, d), kv_index),
                pl.BlockSpec((1, 1, block_k, d), kv_index),
            ],
            out_specs=pl.BlockSpec((1, 1, block_q, d), q_index),
            scratch_shapes=[
                pltpu.VMEM((block_q, 128), jnp.float32),
                pltpu.VMEM((block_q, 128), jnp.float32),
                pltpu.VMEM((block_q, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        interpret=interpret,
    )(offsets, qt, kt, vt)
    return out.transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# Decode attention (single query step against a KV cache)
# ---------------------------------------------------------------------------


def ring_positions(lengths: jnp.ndarray, capacity: int) -> jnp.ndarray:
    """Absolute position held by each row of a window-bounded ROLLING
    (ring) KV cache. A ring cache of `capacity` C stores position p at row
    p mod C, overwriting as the sequence grows, so a slot costs O(window)
    memory instead of O(max_len) (gofr_tpu.kvcache). Row j therefore holds
    the LAST position congruent to j written so far:

        p(j) = t-1 - ((t-1-j) mod C)    for t = lengths tokens written

    p(j) < 0 marks a never-written row (including the whole cache at
    t == 0, where t-1 = -1 makes every p negative). Returns [b, capacity]
    int32."""
    j = jnp.arange(capacity, dtype=jnp.int32)
    t1 = lengths[:, None].astype(jnp.int32) - 1  # [b, 1]
    return t1 - jnp.mod(t1 - j[None, :], capacity)


def decode_attention(
    q: jnp.ndarray,  # [b, 1, hq, d]
    k_cache: jnp.ndarray,  # [b, max_len, hkv, d]
    v_cache: jnp.ndarray,  # [b, max_len, hkv, d]
    lengths: jnp.ndarray,  # [b] int32 — valid prefix length per sequence
    *,
    scale: float | None = None,
    logit_cap: float = 0.0,
    window: int = 0,  # sliding window over absolute positions
    ring: int = 0,  # >0: k/v_cache is a ring of this capacity (kvcache)
) -> jnp.ndarray:
    """Decode is HBM-bandwidth-bound, so the einsums read the cache at its
    STORED dtype (f32 accumulation via preferred_element_type) — routing
    through mha_reference cast the whole cache to f32 first, tripling the
    dominant KV stream (measured r3: 1-layer cost 3x). A hand kernel buys
    nothing beyond this at decode's arithmetic intensity; the
    compiler-friendly einsum form lets XLA fuse the mask and softmax.

    ring > 0 declares the cache a window-bounded ROLLING buffer of that
    capacity (row index = absolute position mod ring, ring == max_len):
    masks are computed from each row's reconstructed absolute position
    instead of its index. Requires window > 0 and ring >= window so every
    in-window position is still resident."""
    b, sq, hq, d = q.shape
    hkv = k_cache.shape[2]
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    max_len = k_cache.shape[1]

    # q * scale stays lossless in bf16 for power-of-two head dims (the only
    # shapes we ship); the f32 path is bitwise-identical either way.
    qg = (q.astype(jnp.float32) * scale).astype(q.dtype)
    qg = qg.reshape(b, sq, hkv, group, d)
    s = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg, k_cache, preferred_element_type=jnp.float32
    )  # [b, hkv, group, sq, max_len]
    if logit_cap > 0.0:
        s = logit_cap * jnp.tanh(s / logit_cap)
    if ring > 0:
        if window <= 0 or ring < window:
            raise ValueError(
                f"ring cache (capacity {ring}) requires 0 < window <= ring, "
                f"got window {window}"
            )
        # ring row j holds absolute position p(j); valid iff ever written
        # (p >= 0) and inside the window ending at the query (abs position
        # lengths-1): p >= lengths - window
        pos = ring_positions(lengths, max_len)  # [b, max_len]
        kv_mask = (pos >= 0) & (pos >= lengths[:, None] - window)
    else:
        kv_mask = jnp.arange(max_len)[None, :] < lengths[:, None]  # [b, max_len]
        if window > 0:
            # query sits at absolute position lengths-1: keep [lengths-window, ..)
            kv_mask = kv_mask & (
                jnp.arange(max_len)[None, :] >= lengths[:, None] - window
            )
    s = jnp.where(kv_mask[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum(
        "bhgqk,bkhd->bqhgd", p, v_cache, preferred_element_type=jnp.float32
    )
    return out.reshape(b, sq, hq, d).astype(q.dtype)


def chunk_decode_attention(
    q: jnp.ndarray,  # [b, 1, hq, d]
    k_cache: jnp.ndarray,  # [b, max_len, hkv, d] — read-only inside a chunk
    v_cache: jnp.ndarray,  # [b, max_len, hkv, d]
    k_buf: jnp.ndarray,  # [b, chunk, hkv, d] — this chunk's new K rows
    v_buf: jnp.ndarray,  # [b, chunk, hkv, d]
    lengths: jnp.ndarray,  # [b] valid main-cache prefix (at chunk START)
    step: jnp.ndarray,  # scalar int32 — current step within the chunk
    *,
    scale: float | None = None,
    logit_cap: float = 0.0,
    window: int = 0,  # sliding window over absolute positions
    ring: int = 0,  # >0: main cache is a rolling ring of this capacity
) -> jnp.ndarray:
    """Decode attention over main cache + chunk ring buffer.

    The serving engine's fused decode chunk never writes the big KV cache at
    per-sequence cursors (a vmap'd scatter XLA lowers terribly — measured
    ~3.5 ms/step across 18 layers, 6x the attention itself). Instead each
    step writes its K/V at the UNIFORM position `step` of a small per-chunk
    buffer (one cheap dynamic_update_slice), the main cache stays read-only,
    and the buffer is merged into per-slot cursor positions ONCE per chunk.
    This function attends over both regions with one joint softmax:
    main positions masked to < lengths, buffer positions masked to <= step.

    ring > 0 declares the MAIN cache a window-bounded rolling buffer of
    that capacity (row index = absolute position mod ring — see
    ring_positions / gofr_tpu.kvcache): main-cache masks derive from each
    row's reconstructed absolute position. The chunk buffer is position-
    indexed either way, so its masks are unchanged.
    """
    b, sq, hq, d = q.shape
    hkv = k_cache.shape[2]
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    max_len, chunk = k_cache.shape[1], k_buf.shape[1]

    qg = (q.astype(jnp.float32) * scale).astype(q.dtype)
    qg = qg.reshape(b, sq, hkv, group, d)
    s_main = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg, k_cache, preferred_element_type=jnp.float32
    )
    s_buf = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg, k_buf, preferred_element_type=jnp.float32
    )
    if logit_cap > 0.0:
        s_main = logit_cap * jnp.tanh(s_main / logit_cap)
        s_buf = logit_cap * jnp.tanh(s_buf / logit_cap)
    if ring > 0:
        if window <= 0 or ring < window:
            raise ValueError(
                f"ring cache (capacity {ring}) requires 0 < window <= ring, "
                f"got window {window}"
            )
        # query's absolute position is lengths + step; ring row j holds
        # absolute position pos(j) <= lengths-1 (causality is implied),
        # valid iff ever written and inside the query's window
        pos = ring_positions(lengths, max_len)  # [b, max_len]
        main_mask = (pos >= 0) & (pos > lengths[:, None] + step - window)
    else:
        main_mask = jnp.arange(max_len)[None, :] < lengths[:, None]  # [b, max_len]
        if window > 0:
            # query's absolute position is lengths + step; main-cache rows
            # live at absolute 0..lengths-1 and buffer row i at lengths + i
            main_mask = main_mask & (
                jnp.arange(max_len)[None, :] > lengths[:, None] + step - window
            )
    buf_mask = jnp.arange(chunk)[None, :] <= step  # [1, chunk]
    if window > 0:
        buf_mask = buf_mask & (jnp.arange(chunk)[None, :] > step - window)
    s_main = jnp.where(main_mask[:, None, None, None, :], s_main, NEG_INF)
    s_buf = jnp.where(buf_mask[:, None, None, None, :], s_buf, NEG_INF)

    # one softmax across both regions without concatenating the caches
    m = jnp.maximum(
        jnp.max(s_main, axis=-1, keepdims=True), jnp.max(s_buf, axis=-1, keepdims=True)
    )
    p_main = jnp.exp(s_main - m)
    p_buf = jnp.exp(s_buf - m)
    denom = jnp.sum(p_main, axis=-1, keepdims=True) + jnp.sum(
        p_buf, axis=-1, keepdims=True
    )
    p_main = (p_main / denom).astype(v_cache.dtype)
    p_buf = (p_buf / denom).astype(v_buf.dtype)
    out = jnp.einsum(
        "bhgqk,bkhd->bqhgd", p_main, v_cache, preferred_element_type=jnp.float32
    ) + jnp.einsum(
        "bhgqk,bkhd->bqhgd", p_buf, v_buf, preferred_element_type=jnp.float32
    )
    return out.reshape(b, sq, hq, d).astype(q.dtype)


def chunk_prefill_attention(
    q: jnp.ndarray,  # [b, c, hq, d] — one prefill chunk's queries
    k_cache: jnp.ndarray,  # [b, capacity, hkv, d] — chunk rows ALREADY written
    v_cache: jnp.ndarray,  # [b, capacity, hkv, d]
    cursors: jnp.ndarray,  # [b] int32 — tokens resident BEFORE this chunk
    *,
    scale: float | None = None,
    logit_cap: float = 0.0,
    window: int = 0,  # sliding window over absolute positions
    ring: int = 0,  # >0: cache is a rolling ring of this capacity (kvcache)
    mesh=None,  # TP mesh the engine serves over (flash path only)
) -> jnp.ndarray:
    """Chunked-prefill attention: a query block at absolute positions
    [cursors, cursors + c) attends every prior key resident in the slot
    cache plus this chunk's own causal triangle — the device-side core of
    the token-budget step scheduler (gofr_tpu.llm), which appends prompts
    into slot KV incrementally instead of prefilling them in one
    monolithic wave.

    The chunk's K/V rows are written into the cache BEFORE this call
    (write-then-attend), so one einsum over the capacity axis covers both
    regions and the softmax needs no two-region merge. Masks are purely
    positional: row p is attended by query i iff p <= cursors + i (causal
    — this also hides any stale rows a previous slot occupant left above
    the cursor) and p > cursors + i - window when windowed. Queries
    beyond the chunk's valid token count produce garbage the engine
    discards; their key rows were never written (the engine drops those
    scatter indices), and causality hides whatever sits there.

    ring > 0 declares the cache a window-bounded rolling buffer of that
    capacity: row positions are reconstructed via ring_positions at the
    post-chunk length (cursors + c), never-written rows come back
    negative, and the same positional masks apply. Requires
    0 < window <= ring - c so a chunk append can never overwrite a row
    still inside any query's window.

    Dots run at the cache's stored dtype with f32 accumulation (the
    decode_attention convention); on the TPU backend with cleanly tiling
    shapes the dense path lowers to the Pallas flash kernel via
    q_offsets (chunks narrower than one 8-row sublane tile — the
    speculative-decoding verify widths, draft + 1 queries — stay on the
    XLA path: a sub-tile block_q has no MXU-aligned lowering).

    SPECULATIVE-DECODING ROLLBACK CONTRACT (gofr_tpu.spec): the verify
    path appends draft rows with this same write-then-attend call and,
    on rejection, rolls the slot cursor back BELOW rows already written.
    Those stale rows are invisible by construction, on both layouts:

    - dense: stale rows sit at positions > every later query's cursor
      until overwritten, and the causal mask (p <= cursors + i) hides
      them — the same property that hides a previous slot occupant's
      rows above the cursor;
    - ring: ring_positions reconstructs row j's position as the LAST
      position congruent to j below the current length, so a stale row
      reads as one full lap (capacity) behind its true position; with
      capacity >= window + chunk that reconstructed position is always
      outside every query's window, and the row is masked until the
      cursor re-reaches it and overwrites it (write-then-attend order).
    """
    b, c, hq, d = q.shape
    hkv = k_cache.shape[2]
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    capacity = k_cache.shape[1]

    qpos = cursors[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]  # [b, c]
    if ring > 0:
        if window <= 0 or ring - c < window:
            # window <= ring - c (the docstring precondition): appending a
            # c-row chunk must never overwrite a row still inside any
            # query's window — violations vanish into the mask silently
            raise ValueError(
                f"ring cache (capacity {ring}) requires 0 < window <= "
                f"ring - chunk ({ring - c}), got window {window}"
            )
        pos = ring_positions(cursors + c, capacity)  # [b, capacity]
        mask = (pos[:, None, :] >= 0) & (pos[:, None, :] <= qpos[:, :, None])
        mask = mask & (pos[:, None, :] > qpos[:, :, None] - window)
    else:
        if not chunk_prefill_why_not_flash(c, capacity, d):
            # dense path on TPU: the flash kernel accepts the query block
            # via per-batch offsets (block_q clamped to the chunk length)
            return flash_attention(
                q, k_cache, v_cache, causal=True, scale=scale,
                logit_cap=logit_cap, window=window,
                block_q=min(128, c), q_offsets=cursors, mesh=mesh,
            )
        kpos = jnp.arange(capacity, dtype=jnp.int32)[None, None, :]
        mask = kpos <= qpos[:, :, None]
        if window > 0:
            mask = mask & (kpos > qpos[:, :, None] - window)

    qg = (q.astype(jnp.float32) * scale).astype(q.dtype)
    qg = qg.reshape(b, c, hkv, group, d)
    s = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg, k_cache, preferred_element_type=jnp.float32
    )  # [b, hkv, group, c, capacity]
    if logit_cap > 0.0:
        s = logit_cap * jnp.tanh(s / logit_cap)
    s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum(
        "bhgqk,bkhd->bqhgd", p, v_cache, preferred_element_type=jnp.float32
    )
    return out.reshape(b, c, hq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Paged attention (block-pool KV; gofr_tpu.kvcache.paged)
# ---------------------------------------------------------------------------
#
# Decode against a BLOCK-PAGED KV pool: per-sequence block tables map
# logical row p to pool row table[p // B] * B + p % B, so the decode read
# stream follows the table instead of a contiguous slab. Two paths,
# selected at trace time exactly like the flash kernel:
#
# - Pallas TPU kernel (_paged_decode_partials): grid (batch,): one program
#   per lane, every kv head at once. The block table and the per-sequence
#   valid band [lo, hi) ride as SCALAR PREFETCH operands; the pool stays in
#   HBM and the program copies pages itself (make_async_copy, double
#   buffered): a GROUP of `pages` table slots at a time, each page the
#   [B, hkv * d] slab at [layer, page] of the pool as it is STORED, the whole
#   stack [L, NB, B, hkv * d] (the layer rides as a fourth scalar prefetch:
#   no layer's pool is sliced out or laid out again before a call), so
#   a step holds pages * B tokens of every head (paged_decode_pages sizes
#   it from the pool's shape and dtype; qwen2: 8 pages, 128 tokens, 128 KB
#   of K and of V). The walk covers only the groups that meet the band,
#   from lo // (pages * B) to where hi ends, and inside an edge group only
#   the pages that meet it: a table entry outside the band is never read,
#   a lane with an empty band costs one empty program. The pool is never
#   gathered into a dense copy, which is the whole point (decode is
#   HBM-bound; a gather would double the dominant stream). Returns
#   online-softmax PARTIALS (normalized output + running max + denom) so
#   the caller can merge the chunk ring buffer region with one rescale.
# - Dense-gather reference (paged_gather): jnp.take the layer's table rows
#   into the contiguous layout and reuse the proven attention above — the
#   off-TPU path and the test oracle. Bit-exact with the
#   contiguous engine because gathering blocks in table order
#   reconstructs the same slab.
#
# int8 KV blocks (TPU_LLM_KV_INT8): the pool stores int8 rows plus one
# f32 scale per (row, kv_head); both paths dequantize after the read, so
# the HBM stream the decode loop is bound by moves at half width. The
# kernel applies a row's scale to its score column and its probability
# (the scale factors out of the dot over d); the scales reach it gathered
# through the table by XLA, 1/32 of the rows' bytes.


def paged_pool_operand(pool_shape, cols: int) -> str:
    """What a paged-decode kernel takes as its HBM operand, named for
    stats()["attention"]["pool_operand"]: the pool as it is stored, every
    layer's, [L, NB, B, W] with a row W = whole heads of `cols` columns
    each (the latent kernel: the one part). Both kernels build their call
    through here, so the array the engine owns IS the operand (pages copied
    from [layer, page] of it); a pool kept in another shape would need a
    view of it written to HBM before every call, and is refused instead."""
    if len(pool_shape) != 4 or pool_shape[-1] % cols:
        raise ValueError(
            f"paged decode reads the pool as stored, [L, NB, B, n * {cols}]; "
            f"got {tuple(pool_shape)}"
        )
    return "whole stack"


def take_pages(pool, layer, tables):
    """[L, NB, B, W] at `layer` through [b, MB] tables -> [b, MB, B, W]:
    ONE gather over the merged (layer, block) axis, so the layer's pool is
    never sliced out first. Table entries clip to the pool like mode="clip".
    `layer` broadcasts against the tables: arange(L)[:, None, None] gathers
    every layer's, [L, b, MB, B, W], the layers outermost as they are wanted
    (a gather along axis 1 with the layers as a batch axis comes out
    blocks-first on the TPU and is transposed after)."""
    L, NB = pool.shape[:2]
    idx = layer * NB + jnp.clip(tables, 0, NB - 1)
    return jnp.take(pool.reshape((L * NB,) + pool.shape[2:]), idx, axis=0)


def _take_scales(sc, layer, tables):
    """The int8 pool's scales [L, NB, B, hkv] at `layer` through the tables
    -> [b, MB, B, hkv]. The layer's slab is indexed out first: f32 of a few
    heads, 1/128 of the layer's rows' bytes, where the stack merged as
    take_pages does would be laid out again whole (its minor dim is too
    narrow for the TPU to keep it minor)."""
    slab = jax.lax.dynamic_index_in_dim(sc, layer, 0, keepdims=False)
    return jnp.take(slab, tables, axis=0, mode="clip")


def paged_gather(k_pool, v_pool, tables, layer, rows, *, k_scales=None, v_scales=None, dtype=None):
    """Layer `layer` of the stored [L, NB, B, h * d] pools -> dense
    [b, MB*B, h, d] views through [b, MB] block tables (the reference read
    path); `rows` = the (h, d) of each pool's rows (kvcache.row_shapes),
    scales [L, NB, B, h]. Stale table entries gather stale blocks — callers
    mask by position exactly as on the contiguous layout."""

    def take(pool, row, sc):
        g = take_pages(pool, layer, tables)  # [b, MB, B, h * d]
        b, MB, B, _ = g.shape
        g = g.reshape((b, MB * B) + tuple(row))
        if sc is not None:
            s = _take_scales(sc, layer, tables).reshape(b, MB * B, row[0])
            g = g.astype(dtype) * s[..., None].astype(dtype)
        return g

    return take(k_pool, rows[0], k_scales), take(v_pool, rows[1], v_scales)


# What one step of the paged-decode kernel holds is derived from the pool's
# own shape: as many tokens of every local kv head as _PAGED_TILE_BYTES of K
# hold at the pool's dtype (as much again of V, both twice over for the
# double buffer), but no fewer than the 128 lanes a score row spans and no
# more than 512 (a step's f32 rows and scores are values the compiler holds).
_PAGED_TILE_BYTES = 128 * 1024
_PAGED_TILE_TOKENS = (128, 512)


def paged_decode_pages(
    block: int, hkv: int, d: int, dtype, table_slots: int, *, hq: int = 0, mesh=None
) -> int:
    """Pages of one lane the paged-decode kernel holds per step (qwen2's
    16-token bf16 pages of 4 heads x 128: 8 pages, 128 tokens), never more
    than the block table has slots. Under a TP mesh the kernel runs per
    head shard: name the mesh (and hq) to count the LOCAL kv heads."""
    if mesh is not None and mesh.size > 1:
        ka = _head_axes(mesh, hq, hkv)[1]
        hkv //= mesh.shape[ka] if ka else 1
    least, most = _PAGED_TILE_TOKENS
    row_bytes = hkv * d * jnp.dtype(dtype).itemsize
    tokens = min(max(_PAGED_TILE_BYTES // row_bytes, least), most)
    return max(1, min(tokens // block, table_slots))


def _paged_decode_kernel(
    # scalar prefetch: block tables + per-sequence valid bounds + the layer
    tbl_ref, lo_ref, hi_ref, layer_ref,
    # q, the pools left in HBM whole ([L, NB, B, hkv * d]: k, v), [the lane's
    # k and v scales, one row per page group], outputs, page buffers, DMA
    # semaphores
    *refs,
    pages: int,
    scale: float,
    logit_cap: float,
    quantized: bool,
):
    if quantized:
        q_ref, k_hbm, v_hbm, ks_ref, vs_ref, o_ref, m_ref, l_ref, k_buf, v_buf, sems = refs
    else:
        q_ref, k_hbm, v_hbm, o_ref, m_ref, l_ref, k_buf, v_buf, sems = refs
        ks_ref = vs_ref = None
    n_pool, block = k_hbm.shape[1], k_hbm.shape[2]
    n_tbl = tbl_ref.shape[1]
    hkv, group, d = q_ref.shape[1:]
    tile = pages * block  # tokens a step
    bi = pl.program_id(0)
    layer = layer_ref[0]
    # the band, held to what the table can name: a wild bound walks no further
    lo = jnp.maximum(lo_ref[bi], 0)
    hi = jnp.minimum(hi_ref[bi], n_tbl * block)
    # the lane's walk: page groups [first, first + n) cover the band [lo, hi)
    first = lo // tile
    n = jnp.where(hi > lo, pl.cdiv(hi, tile) - first, 0)

    def page_copies(g, slot, go):
        """start (go=True) or wait for the copies of group g's LIVE pages
        into buffer `slot`: a page outside the band is never looked up, so
        a stale table entry is never dereferenced."""
        j0 = jnp.maximum(g * pages, lo // block)
        j1 = jnp.minimum((g + 1) * pages, pl.cdiv(hi, block))

        def one(j, carry):
            # a wait only needs the copy's shape, not its source
            page = jnp.clip(tbl_ref[bi, j], 0, n_pool - 1) if go else 0
            for kv, (src, dst) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                cp = pltpu.make_async_copy(
                    src.at[layer, page], dst.at[slot, j - g * pages], sems.at[kv, slot]
                )
                cp.start() if go else cp.wait()
            return carry

        jax.lax.fori_loop(j0, j1, one, 0)

    @pl.when(n > 0)
    def _first():
        page_copies(first, 0, True)

    def rows_of(buf, slot):
        """the step's pages as [tile, hkv * d] f32 rows: a head's are the
        lane-aligned columns [head * d, (head + 1) * d)"""
        return buf[slot].astype(jnp.float32).reshape(tile, hkv * d)

    heads = range(hkv)
    qs = [q_ref[0, h].astype(jnp.float32) * scale for h in heads]  # [group, d]

    # One online-softmax update of every head from one group of pages. The
    # running max / denominator / accumulator are loop-carried VALUES and
    # each phase is written for all heads before the next, so nothing
    # orders one head's matmuls behind another's: on the v5e the same
    # arithmetic through per-head VMEM accumulators took half as long again.
    def body(i, carry):
        m, l, acc = carry
        g = first + i
        slot = i % 2

        @pl.when(i + 1 < n)
        def _next():
            page_copies(g + 1, 1 - slot, True)

        page_copies(g, slot, False)
        base = g * tile  # logical position of the group's first row
        pos = base + jax.lax.broadcasted_iota(jnp.int32, (group, tile), 1)
        in_band = (pos >= lo) & (pos < hi)
        row = base + jax.lax.broadcasted_iota(jnp.int32, (tile, hkv * d), 0)
        row_in_band = (row >= lo) & (row < hi)
        k = rows_of(k_buf, slot)
        s = [
            jax.lax.dot_general(
                qs[h], k[:, h * d:(h + 1) * d], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            for h in heads
        ]  # [group, tile] each
        if quantized:
            # int8 rows: one f32 scale per (row, head) factors out of the
            # dot over d, onto the score's column
            s = [s[h] * ks_ref[0, h, pl.ds(g, 1), :] for h in heads]
        if logit_cap > 0.0:
            s = [logit_cap * jnp.tanh(x / logit_cap) for x in s]
        s = [jnp.where(in_band, x, NEG_INF) for x in s]
        m_new = [jnp.maximum(m[h], jnp.max(s[h], axis=-1, keepdims=True)) for h in heads]
        p = [jnp.exp(s[h] - m_new[h]) for h in heads]
        alpha = [jnp.exp(m[h] - m_new[h]) for h in heads]
        l_new = [alpha[h] * l[h] + jnp.sum(p[h], axis=-1, keepdims=True) for h in heads]
        if quantized:
            p = [
                jnp.where(in_band, p[h] * vs_ref[0, h, pl.ds(g, 1), :], 0.0)
                for h in heads
            ]
        # rows outside the band may be pages never copied: whatever the
        # buffer holds there (NaN included) must not reach 0 * v
        v = jnp.where(row_in_band, rows_of(v_buf, slot), 0.0)
        acc_new = [
            acc[h] * alpha[h] + jax.lax.dot_general(
                p[h], v[:, h * d:(h + 1) * d], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            for h in heads
        ]
        return tuple(m_new), tuple(l_new), tuple(acc_new)

    m, l, acc = jax.lax.fori_loop(0, n, body, (
        tuple(jnp.full((group, 1), NEG_INF, jnp.float32) for _ in heads),
        tuple(jnp.zeros((group, 1), jnp.float32) for _ in heads),
        tuple(jnp.zeros((group, d), jnp.float32) for _ in heads),
    ))
    for h in heads:
        o_ref[0, h] = acc[h] / jnp.where(l[h] == 0.0, 1.0, l[h])
        m_ref[0, h] = jnp.broadcast_to(m[h], m_ref.shape[2:])
        l_ref[0, h] = jnp.broadcast_to(l[h], l_ref.shape[2:])


def _paged_decode_partials(
    q: jnp.ndarray,  # [b, hq, d] one query per sequence
    k_pool: jnp.ndarray,  # [L, NB, B, hkv * d]: the WHOLE pool, as stored
    v_pool: jnp.ndarray,
    tables: jnp.ndarray,  # [b, MB] int32 pool block per logical slot
    lo: jnp.ndarray,  # [b] int32 first valid logical position (window)
    hi: jnp.ndarray,  # [b] int32 one past the last valid position
    layer,  # scalar int32: which layer of the pools this is
    *,
    scale: float,
    logit_cap: float = 0.0,
    k_scales=None,  # [L, NB, B, hkv] f32 (int8 pool)
    v_scales=None,
    interpret: bool = False,
    mesh=None,  # TP mesh: the kernel runs per head shard (_head_axes)
    name: str = "paged_decode",  # what a device trace calls this call's kernel
):
    """Pallas paged-attention decode over the valid band [lo, hi):
    returns (o [b, hq, d] f32 normalized, m [b, hq] f32, l [b, hq] f32)
    online-softmax partials for region merging."""
    hq, hkv = q.shape[1], k_pool.shape[-1] // q.shape[2]
    quantized = k_scales is not None
    layer = jnp.asarray(layer, jnp.int32)
    call = functools.partial(
        _paged_decode_call, scale=scale, logit_cap=logit_cap, interpret=interpret, name=name
    )
    if mesh is None or mesh.size == 1:
        return call(q, k_pool, v_pool, tables, lo, hi, layer, k_scales, v_scales)
    qa, ka = _head_axes(mesh, hq, hkv)
    # a head's columns are contiguous in the stored row: the same heads a
    # shard held as [.., hkv, d] it holds as [.., hkv * d]
    pool_spec = P(None, None, None, ka)
    return jax.shard_map(
        lambda q, k_pool, v_pool, tables, lo, hi, layer, *scales: call(
            q, k_pool, v_pool, tables, lo, hi, layer, *(scales or (None, None))
        ),
        mesh=mesh,
        in_specs=(
            P(None, qa, None), pool_spec, pool_spec, P(), P(), P(), P(),
            *((pool_spec, pool_spec) if quantized else ()),
        ),
        out_specs=(P(None, qa, None), P(None, qa), P(None, qa)),
        check_vma=False,
    )(q, k_pool, v_pool, tables, lo, hi, layer,
      *((k_scales, v_scales) if quantized else ()))


# jitted so that every program that attends at the same shapes (an engine's
# two dozen step and chunk programs) shares ONE trace of the kernel's body
@functools.partial(jax.jit, static_argnames=("scale", "logit_cap", "interpret", "name"))
def _paged_decode_call(
    q, k_pool, v_pool, tables, lo, hi, layer, k_scales, v_scales,
    *, scale: float, logit_cap: float, interpret: bool, name: str = "paged_decode",
):
    """_paged_decode_partials on one device (or one head shard)."""
    b, hq, d = q.shape
    paged_pool_operand(k_pool.shape, d)
    B, W = k_pool.shape[2:]
    hkv = W // d
    MB = tables.shape[1]
    quantized = k_scales is not None
    group = hq // hkv
    pages = paged_decode_pages(B, hkv, d, k_pool.dtype, MB)

    def lane(bi, tbl, lo_, hi_, layer_):
        return (bi, 0, 0, 0)

    # The pools stay in HBM, every layer's, and are the operands AS STORED:
    # the kernel copies whole pages itself from [layer, page], each the
    # [B, hkv * d] slab whose columns [head * d, (head + 1) * d) are a
    # head's rows, lane-aligned. Nothing between the program's parameter and
    # the page copy writes a layer's pool: a custom call's operand is a whole
    # array, so a slice or a view in another tiling would be written to HBM
    # before every call.
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    in_specs = [pl.BlockSpec((1, hkv, group, d), lane), hbm, hbm]
    operands = [q.reshape(b, hkv, group, d), k_pool, v_pool]
    page_buf = pltpu.VMEM((2, pages, B, W), k_pool.dtype)
    if quantized:
        # A page's [B, hkv] scales are too narrow a slab to copy from HBM
        # (Mosaic wants whole 128-lane rows), so XLA gathers the lane's
        # scales through the table, 1/32 of the int8 rows' bytes, and the
        # kernel holds them as one [tile] row per (head, page group).
        n_groups = pl.cdiv(MB, pages)

        def group_rows(sc):  # [L, NB, B, hkv] -> [b, hkv, n_groups, pages * B]
            sc = _take_scales(sc, layer, tables)  # [b, MB, B, hkv]
            sc = jnp.pad(sc, ((0, 0), (0, n_groups * pages - MB), (0, 0), (0, 0)))
            return sc.reshape(b, n_groups, pages * B, hkv).transpose(0, 3, 1, 2)

        in_specs += [pl.BlockSpec((1, hkv, n_groups, pages * B), lane)] * 2
        operands += [group_rows(k_scales), group_rows(v_scales)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, hkv, group, d), lane),
            pl.BlockSpec((1, hkv, group, 128), lane),
            pl.BlockSpec((1, hkv, group, 128), lane),
        ],
        # one DMA semaphore per (k | v, buffer)
        scratch_shapes=[page_buf, page_buf, pltpu.SemaphoreType.DMA((2, 2))],
    )
    kernel = functools.partial(
        _paged_decode_kernel,
        pages=pages, scale=scale, logit_cap=logit_cap, quantized=quantized,
    )
    o, m, l = pl.pallas_call(
        kernel,
        # what a device trace calls the kernel: a mixed stack's window layers
        # call it as paged_decode_window, so the two kinds are told apart
        name=name,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, group, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, group, 128), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, group, 128), jnp.float32),
        ],
        interpret=interpret,
    )(
        tables.astype(jnp.int32), lo.astype(jnp.int32), hi.astype(jnp.int32),
        layer.reshape(1),
        *operands,
    )
    return (
        o.reshape(b, hq, d),
        m[..., 0].reshape(b, hq),
        l[..., 0].reshape(b, hq),
    )


def paged_kernel_why_not(head_dim: int, block: int, *, interpret: bool = False) -> str:
    """Why the Pallas paged-decode kernel cannot serve this config, or ""
    when it can: it needs the TPU backend (or interpret mode for tests),
    a lane-aligned head_dim and a sublane-aligned block size. The one
    decision point for the kernel/XLA-gather choice — the engine reports
    the same answer in stats()["attention"]."""
    if not interpret and jax.default_backend() != "tpu":
        return f"backend {jax.default_backend()} is not tpu"
    if head_dim % 128:
        return f"head_dim {head_dim} is not a multiple of 128"
    if block % 8:
        return f"kv block {block} is not a multiple of 8"
    return ""


def paged_chunk_decode_attention(
    q: jnp.ndarray,  # [b, 1, hq, d]
    k_pool: jnp.ndarray,  # [L, NB, B, hkv * d]: the WHOLE pool, as stored
    v_pool: jnp.ndarray,
    tables: jnp.ndarray,  # [b, MB] int32
    k_buf: jnp.ndarray,  # [b, chunk, hkv, d] — this chunk's new K rows
    v_buf: jnp.ndarray,
    lengths: jnp.ndarray,  # [b] valid pool prefix (at chunk START)
    step: jnp.ndarray,  # scalar int32 — current step within the chunk
    *,
    layer,  # scalar int32: which layer of the pools (and scales) this is
    scale: float | None = None,
    logit_cap: float = 0.0,
    window: int = 0,
    k_scales=None,
    v_scales=None,
    use_kernel: bool | None = None,
    interpret: bool = False,
    mesh=None,  # TP mesh the engine serves over (kernel path only)
    name: str = "paged_decode",  # the kernel's name in a device trace
) -> jnp.ndarray:
    """chunk_decode_attention reading the MAIN region through a block
    table: pool rows hold logical positions [0, lengths) via the table,
    the chunk ring buffer holds positions [lengths, lengths + step]. The
    Pallas path never materializes the gathered cache (partials merged
    with the dense buffer region by one rescale); the reference path
    gathers and defers to chunk_decode_attention — both produce the
    contiguous path's exact masks and dot products, which is what the
    paged==contiguous token-equality tests pin. The pools (and the int8
    pool's scales [L, NB, B, hkv]) come whole, as the engine stores them:
    the kernel copies its pages from `layer` of them, the reference gathers
    the layer's blocks through the table; neither slices a layer's pool."""
    b, sq, hq, d = q.shape
    B = k_pool.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    if use_kernel is None:
        use_kernel = not paged_kernel_why_not(d, B, interpret=interpret)
    if not use_kernel:
        kc, vc = paged_gather(
            k_pool, v_pool, tables, layer, (k_buf.shape[2:], v_buf.shape[2:]),
            k_scales=k_scales, v_scales=v_scales, dtype=q.dtype,
        )
        return chunk_decode_attention(
            q, kc, vc, k_buf, v_buf, lengths, step,
            scale=scale, logit_cap=logit_cap, window=window, ring=0,
        )
    # main region via the paged kernel: valid band [lo, hi)
    hi = lengths
    if window > 0:
        lo = jnp.maximum(lengths + step - window + 1, 0)
    else:
        lo = jnp.zeros_like(lengths)
    o_m, m_m, l_m = _paged_decode_partials(
        q[:, 0], k_pool, v_pool, tables, lo, hi, layer,
        scale=scale, logit_cap=logit_cap,
        k_scales=k_scales, v_scales=v_scales, interpret=interpret, mesh=mesh,
        name=name,
    )
    # buffer region (dense, [b, chunk]) — same mask set as
    # chunk_decode_attention's buffer half
    hkv = k_buf.shape[2]
    group = hq // hkv
    chunk = k_buf.shape[1]
    qg = (q.astype(jnp.float32) * scale).reshape(b, 1, hkv, group, d)
    s_buf = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg, k_buf.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )  # [b, hkv, group, 1, chunk]
    if logit_cap > 0.0:
        s_buf = logit_cap * jnp.tanh(s_buf / logit_cap)
    buf_mask = jnp.arange(chunk)[None, :] <= step
    if window > 0:
        buf_mask = buf_mask & (jnp.arange(chunk)[None, :] > step - window)
    s_buf = jnp.where(buf_mask[:, None, None, None, :], s_buf, NEG_INF)
    m_b = jnp.max(s_buf, axis=-1)  # [b, hkv, group, 1]
    p_buf = jnp.exp(s_buf - m_b[..., None])
    l_b = jnp.sum(p_buf, axis=-1)
    o_b = jnp.einsum(
        "bhgqk,bkhd->bhgqd", p_buf, v_buf.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )  # [b, hkv, group, 1, d] — UNNORMALIZED (divided below)
    m_b = m_b.reshape(b, hq)
    l_b = l_b.reshape(b, hq)
    o_b = o_b.reshape(b, hq, d)
    # merge the two regions' online-softmax partials
    m = jnp.maximum(m_m, m_b)
    a_m = jnp.exp(m_m - m) * l_m
    a_b = jnp.exp(m_b - m)
    denom = a_m + a_b * l_b
    denom = jnp.where(denom == 0.0, 1.0, denom)
    out = (o_m * a_m[..., None] + o_b * a_b[..., None]) / denom[..., None]
    return out[:, None].astype(q.dtype)


# ---------------------------------------------------------------------------
# Latent (MLA) attention, absorbed: every query head over ONE shared row
# ---------------------------------------------------------------------------
#
# A latent cache row is [c_kv | k_rope]: the normalized compressed key/value
# (kv_lora_rank values) and the rotated rope key that every head shares. With
# the up-projection absorbed into the query (q_abs = q_nope W_uk^T) attention
# is multi-query over that one row: score = (q_abs . c_kv + q_rope . k_rope)
# * scale, and the probabilities weigh c_kv itself (o_lat, kv_lora_rank wide;
# the caller applies W_uv). The pool keeps the two parts as two arrays,
# c_kv [L, NB, B, C] and k_rope [L, NB, B, R] with R padded to whole
# 128-lane rows (latent_rope_width), so that a page of either is a slab
# Mosaic copies and every (k, v)-shaped pool helper moves them unchanged (a
# row outside the pool is [1, C] / [1, R]: kvcache.row_shapes); no array
# holds values. Queries arrive as ONE array [.., hq, C + R], split here.


def latent_rope_width(rope_dim: int) -> int:
    """Columns the pool keeps for the shared rope key: whole 128-lane rows."""
    return -(-int(rope_dim) // 128) * 128


def latent_attention(
    q: jnp.ndarray,  # [b, s, hq, C + R]  (q_abs | q_rope, zero-padded to R)
    rows_c: jnp.ndarray,  # [b, n, 1, C]
    rows_r: jnp.ndarray,  # [b, n, 1, R]
    mask: jnp.ndarray,  # [b, s, n] bool: which rows each query attends
    *,
    scale: float,
) -> jnp.ndarray:
    """Absorbed latent attention in XLA: -> o_lat [b, s, hq, C]. Dots run at
    the rows' stored dtype with f32 accumulation (the decode_attention
    convention). The prefill path inside the fused step, the off-TPU decode
    path and the kernel's test oracle."""
    C = rows_c.shape[-1]
    rc, rr = rows_c[:, :, 0], rows_r[:, :, 0]
    qs = (q.astype(jnp.float32) * scale).astype(rc.dtype)
    s = jnp.einsum(
        "bshc,bnc->bhsn", qs[..., :C], rc, preferred_element_type=jnp.float32
    ) + jnp.einsum(
        "bshr,bnr->bhsn", qs[..., C:], rr, preferred_element_type=jnp.float32
    )
    s = jnp.where(mask[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(rc.dtype)
    out = jnp.einsum("bhsn,bnc->bshc", p, rc, preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def latent_chunk_prefill_attention(q, c_cache, r_cache, cursors, *, scale):
    """chunk_prefill_attention for latent rows: queries at positions
    [cursors, cursors + c) over every resident row (the chunk's own already
    written), causal by position."""
    c, capacity = q.shape[1], c_cache.shape[1]
    qpos = cursors[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    kpos = jnp.arange(capacity, dtype=jnp.int32)[None, None, :]
    return latent_attention(q, c_cache, r_cache, kpos <= qpos[:, :, None], scale=scale)


def mla_kernel_why_not(latent: int, rope: int, block: int, *, interpret: bool = False) -> str:
    """paged_kernel_why_not for the latent kernel: lane-aligned parts and a
    page that is whole bf16 sublane tiles."""
    if not interpret and jax.default_backend() != "tpu":
        return f"backend {jax.default_backend()} is not tpu"
    if latent % 128 or rope % 128:
        return f"latent row ({latent} | {rope}) is not whole 128-lane parts"
    if block % 16:
        return f"kv block {block} is not a multiple of 16"
    return ""


def _mla_paged_decode_kernel(
    # scalar prefetch: block tables + per-sequence valid bounds + the layer
    tbl_ref, lo_ref, hi_ref, layer_ref,
    # the lane's queries (pre-scaled, the pool's dtype), the two pools left in
    # HBM whole ([L, NB, B, C | R]), outputs, page buffers, DMA semaphores
    qc_ref, qr_ref, c_hbm, r_hbm, o_ref, m_ref, l_ref, c_buf, r_buf, sems,
    *,
    pages: int,
):
    """_paged_decode_kernel for latent rows: one program a lane, pages
    copied by the kernel (double buffered), only the lane's band. Every
    query head meets the same rows, so a step is two matmuls of all heads:
    scores [hq, tile] from the c_kv and rope parts, then p @ c_kv."""
    n_pool, block = c_hbm.shape[1], c_hbm.shape[2]
    n_tbl = tbl_ref.shape[1]
    hq, C = qc_ref.shape[1:]
    R = qr_ref.shape[2]
    tile = pages * block
    bi = pl.program_id(0)
    layer = layer_ref[0]
    lo = jnp.maximum(lo_ref[bi], 0)
    hi = jnp.minimum(hi_ref[bi], n_tbl * block)
    first = lo // tile
    n = jnp.where(hi > lo, pl.cdiv(hi, tile) - first, 0)

    def page_copies(g, slot, go):
        j0 = jnp.maximum(g * pages, lo // block)
        j1 = jnp.minimum((g + 1) * pages, pl.cdiv(hi, block))

        def one(j, carry):
            page = jnp.clip(tbl_ref[bi, j], 0, n_pool - 1) if go else 0
            for part, (src, dst) in enumerate(((c_hbm, c_buf), (r_hbm, r_buf))):
                cp = pltpu.make_async_copy(
                    src.at[layer, page], dst.at[slot, j - g * pages], sems.at[part, slot]
                )
                cp.start() if go else cp.wait()
            return carry

        jax.lax.fori_loop(j0, j1, one, 0)

    @pl.when(n > 0)
    def _first():
        page_copies(first, 0, True)

    qc, qr = qc_ref[0], qr_ref[0]  # [hq, C], [hq, R]
    nt = (((1,), (1,)), ((), ()))

    def body(i, carry):
        m, l, acc = carry
        g = first + i
        slot = i % 2

        @pl.when(i + 1 < n)
        def _next():
            page_copies(g + 1, 1 - slot, True)

        page_copies(g, slot, False)
        base = g * tile
        pos = base + jax.lax.broadcasted_iota(jnp.int32, (hq, tile), 1)
        in_band = (pos >= lo) & (pos < hi)
        row = base + jax.lax.broadcasted_iota(jnp.int32, (tile, C), 0)
        c = c_buf[slot].reshape(tile, C)
        r = r_buf[slot].reshape(tile, R)
        s = jax.lax.dot_general(qc, c, nt, preferred_element_type=jnp.float32)
        s = s + jax.lax.dot_general(qr, r, nt, preferred_element_type=jnp.float32)
        s = jnp.where(in_band, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        # rows outside the band may be pages never copied: whatever the
        # buffer holds there (NaN included) must not reach 0 * v
        v = jnp.where((row >= lo) & (row < hi), c, jnp.zeros_like(c))
        acc_new = acc * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, n, body, (
        jnp.full((hq, 1), NEG_INF, jnp.float32),
        jnp.zeros((hq, 1), jnp.float32),
        jnp.zeros((hq, C), jnp.float32),
    ))
    o_ref[0] = acc / jnp.where(l == 0.0, 1.0, l)
    m_ref[0] = jnp.broadcast_to(m, m_ref.shape[1:])
    l_ref[0] = jnp.broadcast_to(l, l_ref.shape[1:])


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _mla_paged_decode_call(q, c_pool, r_pool, tables, lo, hi, layer, *, scale: float, interpret: bool):
    """Partials of the main region of layer `layer` of the WHOLE pools
    [L, NB, B, C | R], the operands as stored: (o [b, hq, C] f32
    normalized, m, l [b, hq] f32). Query heads are padded to whole bf16
    sublane tiles."""
    b, hq, _ = q.shape
    R = r_pool.shape[-1]
    paged_pool_operand(c_pool.shape, q.shape[-1] - R)
    B, C = c_pool.shape[2:]
    MB = tables.shape[1]
    hp = -(-hq // 16) * 16
    pages = paged_decode_pages(B, 1, C, c_pool.dtype, MB)
    qs = jnp.pad((q.astype(jnp.float32) * scale).astype(c_pool.dtype),
                 ((0, 0), (0, hp - hq), (0, 0)))

    def lane(bi, tbl, lo_, hi_, layer_):
        return (bi, 0, 0)

    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, hp, C), lane), pl.BlockSpec((1, hp, R), lane), hbm, hbm],
        out_specs=[
            pl.BlockSpec((1, hp, C), lane),
            pl.BlockSpec((1, hp, 128), lane),
            pl.BlockSpec((1, hp, 128), lane),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, pages, B, C), c_pool.dtype),
            pltpu.VMEM((2, pages, B, R), r_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    o, m, l = pl.pallas_call(
        functools.partial(_mla_paged_decode_kernel, pages=pages),
        name="mla_paged_decode",  # what a device trace calls the kernel
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hp, C), jnp.float32),
            jax.ShapeDtypeStruct((b, hp, 128), jnp.float32),
            jax.ShapeDtypeStruct((b, hp, 128), jnp.float32),
        ],
        interpret=interpret,
    )(
        tables.astype(jnp.int32), lo.astype(jnp.int32), hi.astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1),
        qs[..., :C], qs[..., C:], c_pool, r_pool,
    )
    return o[:, :hq], m[:, :hq, 0], l[:, :hq, 0]


def mla_paged_chunk_decode_attention(
    q: jnp.ndarray,  # [b, 1, hq, C + R]
    c_pool: jnp.ndarray,  # [L, NB, B, C]: the WHOLE pool, as stored
    r_pool: jnp.ndarray,  # [L, NB, B, R]
    tables: jnp.ndarray,  # [b, MB] int32
    c_buf: jnp.ndarray,  # [b, chunk, 1, C] — this chunk's new rows
    r_buf: jnp.ndarray,  # [b, chunk, 1, R]
    lengths: jnp.ndarray,  # [b] valid pool prefix (at chunk START)
    step: jnp.ndarray,  # scalar int32 — current step within the chunk
    *,
    scale: float,
    layer,  # scalar int32: which layer of the pools this is
    use_kernel: bool | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """paged_chunk_decode_attention for latent rows -> o_lat [b, 1, hq, C]:
    pool rows hold positions [0, lengths) through the table, the chunk
    buffer [lengths, lengths + step]. Kernel path: the main region's
    partials merged with the dense buffer region by one rescale; else the
    rows are gathered and both regions share one softmax. The pools come
    whole and the kernel copies its pages from `layer` of them: a layer's
    pool sliced out of the stack is a copy of it before every call (a custom
    call's operand is a whole array)."""
    b, _, hq, _ = q.shape
    B, C = c_pool.shape[-2:]
    chunk = c_buf.shape[1]
    buf_mask = jnp.broadcast_to(jnp.arange(chunk)[None, None, :] <= step, (b, 1, chunk))
    if use_kernel is None:
        use_kernel = not mla_kernel_why_not(C, r_pool.shape[-1], B, interpret=interpret)
    if not use_kernel:
        cc, rc = paged_gather(
            c_pool, r_pool, tables, layer, (c_buf.shape[2:], r_buf.shape[2:])
        )
        main_mask = jnp.arange(cc.shape[1])[None, None, :] < lengths[:, None, None]
        return latent_attention(
            q, jnp.concatenate([cc, c_buf.astype(cc.dtype)], axis=1),
            jnp.concatenate([rc, r_buf.astype(rc.dtype)], axis=1),
            jnp.concatenate([main_mask, buf_mask], axis=-1), scale=scale,
        )
    o_m, m_m, l_m = _mla_paged_decode_call(
        q[:, 0], c_pool, r_pool, tables, jnp.zeros_like(lengths), lengths, layer,
        scale=scale, interpret=interpret,
    )
    qs = q[:, 0].astype(jnp.float32) * scale
    cb, rb = c_buf[:, :, 0].astype(jnp.float32), r_buf[:, :, 0].astype(jnp.float32)
    s_buf = jnp.einsum("bhc,bkc->bhk", qs[..., :C], cb) + jnp.einsum(
        "bhr,bkr->bhk", qs[..., C:], rb
    )
    s_buf = jnp.where(buf_mask, s_buf, NEG_INF)
    m_b = jnp.max(s_buf, axis=-1)
    p_buf = jnp.exp(s_buf - m_b[..., None])
    l_b = jnp.sum(p_buf, axis=-1)
    o_b = jnp.einsum("bhk,bkc->bhc", p_buf, cb)  # unnormalized
    m = jnp.maximum(m_m, m_b)
    a_m = jnp.exp(m_m - m) * l_m
    a_b = jnp.exp(m_b - m)
    denom = a_m + a_b * l_b
    denom = jnp.where(denom == 0.0, 1.0, denom)
    out = (o_m * a_m[..., None] + o_b * a_b[..., None]) / denom[..., None]
    return out[:, None].astype(q.dtype)


# ---------------------------------------------------------------------------
# Dispatcher
# ---------------------------------------------------------------------------


def flash_why_not(sq: int, sk: int, head_dim: int, block_q: int, block_k: int) -> str:
    """Why the Pallas flash kernel cannot serve these shapes, or "" when
    it can (TPU backend, sequences that divide their blocks, lane-aligned
    head_dim)."""
    if jax.default_backend() != "tpu":
        return f"backend {jax.default_backend()} is not tpu"
    if head_dim % 128:
        return f"head_dim {head_dim} is not a multiple of 128"
    if sq % block_q or sk % block_k:
        return f"seq lengths ({sq},{sk}) do not divide blocks ({block_q},{block_k})"
    return ""


def chunk_prefill_why_not_flash(chunk: int, capacity: int, head_dim: int) -> str:
    """flash_why_not for a dense chunk append of `chunk` queries against a
    `capacity`-row slot cache (block_q clamped to the chunk). Chunks
    narrower than one 8-row sublane tile — the speculative verify widths
    — have no MXU-aligned block_q. The engine reports the same answer in
    stats()["attention"]."""
    if chunk % 8:
        return f"chunk {chunk} is not a multiple of 8"
    return flash_why_not(chunk, capacity, head_dim, min(128, chunk), 128)


def multi_head_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    scale: float | None = None,
    logit_cap: float = 0.0,
    kv_mask: jnp.ndarray | None = None,
    q_positions: jnp.ndarray | None = None,
    window: int = 0,
    block_q: int = 128,
    block_k: int = 128,
) -> jnp.ndarray:
    """Platform dispatcher: Pallas flash kernel on TPU when shapes tile
    cleanly onto the MXU (including banded/sliding-window prefill, where
    the kernel skips blocks behind the band), XLA reference otherwise.
    kv_mask/q_positions force the reference path (the flash kernel
    assumes dense right-aligned prefill)."""
    if (
        kv_mask is None and q_positions is None
        and not flash_why_not(q.shape[1], k.shape[1], q.shape[3], block_q, block_k)
    ):
        return flash_attention(
            q, k, v, causal=causal, scale=scale, logit_cap=logit_cap,
            window=window, block_q=block_q, block_k=block_k,
        )
    return mha_reference(
        q, k, v, causal=causal, scale=scale, logit_cap=logit_cap,
        kv_mask=kv_mask, q_positions=q_positions, window=window,
    )

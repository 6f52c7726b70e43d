"""Latent (MLA) attention over the paged latent cache, the dropless routed FFN
and the layer groups, on the CPU at the float32 twin with seeded weights.

Compared on LOGITS against the benchmark's plain reference (expanded form,
float32; benchmarks/latent_moe_reference.py), which shares no code with the
program. Tolerances, and why:

- `EXACT` 2e-4 on logits of order 1: float32 on both sides, the same int8
  weights, weight-only matmuls (`qmm`); what is left is the order of float32
  additions (absorbed against expanded, paged against whole-sequence, a grouped
  matmul against a per-expert loop). Measured at most 6e-6. Any missing term
  (a rope half, a norm, the scale 1.8, a dropped pair) moves logits by 1e-2
  and more.
- prompt chunks are served W8A8 (`qmm_a8`, as the dense families): int8
  activations move a logit by up to ~1 at these tiny widths and flip a
  router's near ties, so those programs are compared weight-only here (the
  test swaps `qmm_a8` for `qmm`), and as served by the benchmark's own gap
  check (benchmarks/tests, `--rehearse`).
"""

import hashlib
import math
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.kvcache import CacheManager, row_shapes
from gofr_tpu.kvcache.paged import gather_slots, scatter_rows
from gofr_tpu.llm import GenRequest, LLMEngine, latent_refusal
from gofr_tpu.models import TransformerConfig, init_params
from gofr_tpu.models import moe as M
from gofr_tpu.models import transformer as T
from gofr_tpu.models.quant import QTensor, qmm, quantize_params
from gofr_tpu.ops import attention as A

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
EXACT = 2e-4
SEED = 2**31 + 29
BLOCK = 16


def _bench(name):
    """A module of benchmarks/ (the plain reference's side), by name."""
    sys.path[:0] = [BENCH]
    try:
        return __import__(name)
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def twin():
    """(model group, family module, program config, program params) of the
    GLM-4.7-Flash configuration's CPU twin."""
    run = _bench("run")
    with open(os.path.join(BENCH, "configs", "glm-4.7-flash.json")) as f:
        cfg_file = json.load(f)
    model = cfg_file["rehearsal"]["model"]
    family = run.load_family(cfg_file)
    return model, family, family.program_config(model), family.program_params(model, SEED)


@pytest.fixture()
def weight_only(monkeypatch):
    """Prompt chunks with weight-only matmuls (see the module's docstring)."""
    monkeypatch.setattr(T, "qmm_a8", qmm)


def _tokens(n, s, vocab=512, seed=0):
    return np.random.default_rng(seed).integers(1, vocab, (n, s)).astype(np.int32)


# -- the whole-sequence forward pass: absorbed equals expanded -------------------


def test_absorbed_equals_expanded(twin, weight_only):
    """The program's attention is the absorbed form (q_nope W_uk^T against the
    normalized latent, W_uv after the weighted sum); the reference writes every
    head's keys and values out. Same logits."""
    model, family, cfg, params = twin
    toks = _tokens(2, 40)
    pos = jnp.broadcast_to(jnp.arange(40), (2, 40))
    got, _ = jax.jit(lambda p, t: T.transformer_forward(p, cfg, t, pos))(params, jnp.asarray(toks))
    want = np.asarray(family.forward_logits(model, SEED, toks))
    assert np.abs(np.asarray(got) - want).max() < EXACT
    assert want.std() > 0.5  # logits of order 1: the tolerance means something


# -- prefill, then decode, through the paged latent cache ----------------------------


def _forced(forced):
    """A sampler that records each step's logits and feeds the GIVEN next token
    (teacher forcing), so that every position can be held to the reference."""

    def sample(logits, temps, key, state):
        i, buf = state
        return forced[i], (i + 1, buf.at[i].set(logits))

    return sample


def _serve_paged(cfg, params, seqs, prompt_lens, tables, *, chunk=8, K=4, shared=0, use_kernel=False):
    """Serve `seqs` [b, total] as the engine's programs do: prompt chunks through
    gather_slots -> prefill_append -> scatter_rows (lane i from cursor `shared`
    where its table shares lane 0's blocks), then decode chunks of K through
    decode_chunk_paged, every token forced. Returns {(lane, position): logits}."""
    b, total = seqs.shape
    (k_row, v_row), L = row_shapes(cfg), cfg.n_layers
    NB = int(tables.max()) + 1
    pool = T.KVCache(k=jnp.zeros((L, NB, BLOCK, math.prod(k_row)), cfg.dtype),  # as stored: a row flat
                     v=jnp.zeros((L, NB, BLOCK, math.prod(v_row)), cfg.dtype), length=jnp.zeros((b,), jnp.int32))
    tables = jnp.asarray(tables)
    out = {}
    cursors = np.array([0] + [shared] * (b - 1)) if shared else np.zeros((b,), np.int64)
    while (cursors < prompt_lens).any():
        n_new = np.minimum(chunk, prompt_lens - cursors)
        if cursors[0] < shared:  # the shared blocks are read once their owner has written them
            n_new[1:] = 0
        toks = np.zeros((b, chunk), np.int32)
        for i in range(b):
            toks[i, : n_new[i]] = seqs[i, cursors[i] : cursors[i] + n_new[i]]
        cur, nn = jnp.asarray(cursors, jnp.int32), jnp.asarray(n_new, jnp.int32)
        sub = gather_slots(pool.k, pool.v, tables, cur, rows=(k_row, v_row))
        logits, sub2 = T.prefill_append(params, cfg, jnp.asarray(toks), sub, cur, nn)
        pos = cur[:, None] + jnp.arange(chunk, dtype=jnp.int32)[None, :]
        rows = [jnp.take_along_axis(a, pos[None, :, :, None, None], axis=2) for a in (sub2.k, sub2.v)]
        k2, v2, _ = scatter_rows(pool.k, pool.v, tables, rows[0], rows[1], pos,
                                 jnp.arange(chunk)[None, :] < nn[:, None])
        pool = T.KVCache(k=k2, v=v2, length=cur + nn)
        for i in range(b):
            if n_new[i] > 0:
                out[i, int(cursors[i] + n_new[i] - 1)] = np.asarray(logits[i])
        cursors = cursors + n_new
    lengths = np.array(prompt_lens)
    tail = jnp.asarray([seqs[i, lengths[i]] for i in range(b)], jnp.int32)
    while (lengths + K < total).all():
        forced = jnp.asarray(np.stack([seqs[np.arange(b), lengths + j + 1] for j in range(K)]), jnp.int32)
        state = (jnp.int32(0), jnp.zeros((K, b, cfg.vocab_size), jnp.float32))
        _toks, tail, pool, _sc, _rng, (_, buf) = T.decode_chunk_paged(
            params, cfg, tail, pool, None, tables, jnp.ones((b,), bool), jnp.zeros((b,)), jax.random.PRNGKey(0),
            n_steps=K, sample_fn=_forced(forced), block=BLOCK, use_kernel=use_kernel, sample_state=state)
        for j in range(K):
            for i in range(b):
                out[i, int(lengths[i] + j)] = np.asarray(buf[j, i])
        lengths = lengths + K
    return out


def test_prefill_then_decode_through_the_paged_latent_cache(twin, weight_only):
    """Two lanes of different lengths: prompts of 21 and 37 tokens in chunks of 8
    (across the block boundaries at 16 and 32), then 20 forced decode tokens in
    chunks of 4 (lane 0 crosses 32, lane 1 crosses 48), blocks scattered over
    the pool. Every logit the programs give is the reference's."""
    model, family, cfg, params = twin
    seqs = _tokens(2, 64, seed=3)
    tables = np.random.default_rng(1).permutation(8).reshape(2, 4).astype(np.int32)
    got = _serve_paged(cfg, params, seqs, np.array([21, 37]), tables)
    want = np.asarray(family.forward_logits(model, SEED, seqs))
    assert {(0, 20), (1, 36), (0, 21), (0, 40), (1, 37), (1, 56)} <= set(got)
    worst = max(np.abs(lg - want[i, p]).max() for (i, p), lg in got.items())
    assert worst < EXACT, worst


def test_a_shared_prefix_is_read_in_place(twin, weight_only):
    """Radix reuse as the engine seeds it: lane 1's table names lane 0's first
    two blocks and its prefill starts at cursor 32. Its logits are those of its
    whole sequence."""
    model, family, cfg, params = twin
    seqs = _tokens(2, 60, seed=5)
    seqs[1, :32] = seqs[0, :32]
    tables = np.array([[3, 5, 0, 6], [3, 5, 2, 1]], np.int32)
    got = _serve_paged(cfg, params, seqs, np.array([40, 44]), tables, shared=32)
    want = np.asarray(family.forward_logits(model, SEED, seqs))
    worst = max(np.abs(lg - want[i, p]).max() for (i, p), lg in got.items())
    assert (1, 43) in got and (1, 55) in got and worst < EXACT, worst


def test_the_engine_serves_it_and_shares_a_prefix(twin):
    """Through LLMEngine as the benchmark drives it (W8A8 prompt chunks): greedy
    tokens of a request are the same with and without a radix-shared prefix, and
    the step records count the experts' pairs."""
    _model, _family, cfg, params = twin
    kw = dict(slots=2, max_seq_len=128, prefill_chunk=8, prefill_buckets=(8,), step_token_budget=16,
              decode_chunk=4, warmup=False, quantize=True)
    first, second = list(range(1, 41)), list(range(1, 33)) + [77, 78, 79]
    alone = LLMEngine(cfg, params, **kw)
    try:
        want = alone.generate(second, max_new_tokens=10)
    finally:
        alone.close()
    eng = LLMEngine(cfg, params, prefix_cache_mb=1, **kw)
    try:
        eng.generate(first, max_new_tokens=4)
        assert eng.generate(second, max_new_tokens=10) == want
        st = eng.stats()
        assert st["kvcache"]["prefix"]["hits"] + st["kvcache"]["prefix"].get("partial_hits", 0) >= 1
        assert st["attention"]["decode"].startswith("xla_gather") and st["moe_experts"] == 8
        fields = st["step_log"]["fields"]
        n_moe, k = cfg.n_layers - cfg.n_dense_layers, cfg.moe_top_k
        for rec in map(lambda r: dict(zip(fields, r)), st["step_log"]["records"]):
            # every lane runs every iteration, a row every one of its columns
            tokens = rec["k"] * 2 + sum(shape for _start, _n, shape in rec["rows"])
            assert rec["moe_pairs"] == tokens * k * n_moe, rec
            assert 0 < rec["moe_touched"] <= 8 * n_moe * (rec["k"] + (rec["kind"] == "step"))
        moe = st["moe"]
        assert moe["experts"] == "ragged_dot (backend cpu is not tpu)"  # the path is named, as attention's is
        assert moe["pairs"] == sum(moe["tokens_per_expert"]) and len(moe["tokens_per_expert"]) == 8
        assert moe["layer_calls"] == n_moe * sum(r[fields.index("k")] + (r[fields.index("kind")] == "step")
                                                 for r in st["step_log"]["records"])
        assert st["kvcache"]["row_bytes"] == (32 + 128) * 4  # latent + the rope key's 128 lanes, float32
    finally:
        eng.close()


# -- the latent decode kernel ----------------------------------------------------------


# Each lane's band [lo, hi) and the table's slots. The test's f32 rows of
# 128 | 128 make a page group 128 tokens (8 pages) and keep 7 groups in
# flight (mla_decode_schedule); the kernel walks every lane's groups as ONE
# stream of copies, so what a band looks like next to its neighbours matters.
LATENT_BANDS = {
    "an empty band, a part page, the last page group": ([0, 0, 0], [0, 37, 190], 12),
    "an empty lane between live ones": ([0] * 6, [150, 0, 70, 0, 0, 129], 12),
    "every lane empty": ([0] * 3, [0] * 3, 12),
    "one lane alone": ([0], [100], 12),
    "bands ending on a group boundary and of one token": ([0] * 4, [128, 1, 256, 16], 16),
    "lanes ten times apart": ([0] * 4, [30, 300, 29, 310], 20),
    "more groups than buffers in flight": ([0] * 6, [380, 250, 0, 384, 130, 200], 24),
    "lo > 0": ([5, 130, 0, 255, 200], [100, 300, 40, 384, 201], 24),
    "a table narrower than a group": ([0, 0, 3], [90, 17, 96], 6),
}


def _latent_inputs(b, MB, hq=5, C=128, R=128, seed=1):
    """q [b, 1, hq, C + R], the pools as stored: a stack of two layers
    [2, NB, B, C | R] (read at the second), a rope key of 8 columns padded
    to R, tables over the pool in random order, and the chunk's rows."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    NB = b * MB
    c_pool = jax.random.normal(keys[0], (NB, BLOCK, C), jnp.float32)
    r_pool = jnp.pad(jax.random.normal(keys[1], (NB, BLOCK, 8), jnp.float32), ((0, 0),) * 2 + ((0, R - 8),))
    c_pool, r_pool = (jnp.stack([jnp.zeros_like(a), a]) for a in (c_pool, r_pool))
    q = jax.random.normal(keys[2], (b, 1, hq, C + R), jnp.float32)
    tables = np.random.default_rng(seed).permutation(NB).reshape(b, MB).astype(np.int32)
    c_buf = jax.random.normal(keys[3], (b, 4, 1, C), jnp.float32)
    r_buf = jnp.pad(jax.random.normal(keys[4], (b, 4, 1, 8), jnp.float32), ((0, 0),) * 3 + ((0, R - 8),))
    return q, c_pool, r_pool, tables, c_buf, r_buf


def _band_partials(q, c_pool, r_pool, tables, lo, hi, layer, scale):
    """The kernel's partials over [lo, hi) from the gathered rows, by XLA:
    (o normalized, m, l); an empty band gives l = 0 and no o or m."""
    C = c_pool.shape[-1]
    rc, rr = (a[:, :, 0] for a in A.paged_gather(c_pool, r_pool, tables, layer, ((1, C), (1, r_pool.shape[-1]))))
    qs = q * scale
    s = jnp.einsum("bhc,bnc->bhn", qs[..., :C], rc) + jnp.einsum("bhr,bnr->bhn", qs[..., C:], rr)
    pos = jnp.arange(rc.shape[1])
    live = (pos >= jnp.asarray(lo)[:, None]) & (pos < jnp.asarray(hi)[:, None])
    s = jnp.where(live[:, None], s, -jnp.inf)
    m = jnp.max(s, axis=-1)
    p = jnp.where(live[:, None], jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    return jnp.einsum("bhn,bnc->bhc", p, rc) / jnp.where(l == 0, 1.0, l)[..., None], m, l


def _in_time(fn, seconds=300):
    """fn() on a thread of its own: the DMA simulator blocks for ever on a
    wait for a copy that was never started, which fails here instead."""
    import threading

    out = []

    def run():
        try:
            out.append(jax.block_until_ready(fn()))
        except Exception as e:  # noqa: BLE001 — raised again on the test's thread
            out.append(e)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(seconds)
    assert out, f"the kernel's copy stream did not finish in {seconds} s: a wait without its copy"
    if isinstance(out[0], Exception):
        raise out[0]
    return out[0]


def _simulated_partials(q, c_pool, r_pool, tables, lo, hi, layer, scale):
    """The kernel's partials in the TPU interpreter that simulates its DMAs
    and semaphores (a copy lands when it is waited for; a read outside a
    buffer raises; VMEM starts as NaN)."""
    from jax.experimental.pallas import tpu as pltpu

    return _in_time(lambda: A._mla_paged_decode_call(
        q, c_pool, r_pool, jnp.asarray(tables), jnp.asarray(lo, jnp.int32), jnp.asarray(hi, jnp.int32),
        layer, scale=scale, interpret=pltpu.InterpretParams(),
    ))


def _assert_partials_equal(got, want, hi, lo):
    empty = np.asarray(hi) <= np.asarray(lo)
    o, m, l = (np.asarray(x) for x in got)
    wo, wm, wl = (np.asarray(x) for x in want)
    assert (l[empty] == 0).all() and (o[empty] == 0).all()
    live = ~empty
    np.testing.assert_allclose(o[live], wo[live], atol=1e-5)
    np.testing.assert_allclose(m[live], wm[live], rtol=1e-6, atol=1e-5)
    np.testing.assert_allclose(l[live], wl[live], rtol=1e-5)


@pytest.mark.parametrize("case", list(LATENT_BANDS))
def test_the_latent_kernel_in_interpret_mode_equals_the_dense_gather(case):
    """The kernel against the gathered rows, band by band: the main region's
    partials over [lo, hi) with its copies simulated, and where every band
    starts at 0 the whole chunk-decode (main region and the chunk's own rows)
    against the gather path's one softmax."""
    lo, hi, MB = LATENT_BANDS[case]
    b = len(hi)
    q, c_pool, r_pool, tables, c_buf, r_buf = _latent_inputs(b, MB)
    sched = A.mla_decode_schedule(BLOCK, 128, 128, jnp.float32, MB)
    groups = sum(-(-h // sched.tokens) - l_ // sched.tokens for l_, h in zip(lo, hi) if h > l_)
    if case == "more groups than buffers in flight":
        assert groups > sched.in_flight + 1 >= 3
    got = _simulated_partials(q[:, 0], c_pool, r_pool, tables, lo, hi, 1, 0.1)
    _assert_partials_equal(got, _band_partials(q[:, 0], c_pool, r_pool, tables, lo, hi, 1, 0.1), hi, lo)
    if not any(lo):
        args = (q, c_pool, r_pool, jnp.asarray(tables), c_buf, r_buf, jnp.asarray(hi, jnp.int32), jnp.int32(2))
        kernel = A.mla_paged_chunk_decode_attention(*args, scale=0.1, layer=1, use_kernel=True, interpret=True)
        gather = A.mla_paged_chunk_decode_attention(*args, scale=0.1, layer=1, use_kernel=False)
        assert kernel.shape == (b, 1, 5, 128) and float(jnp.abs(kernel - gather).max()) < 1e-5


def test_the_latent_kernel_never_reads_a_dead_page():
    """NaN in every pool block outside the lanes' bands and in the whole of
    the other layer, and every table entry outside a band (before lo and
    past hi) an id outside the pool: the partials are finite and equal
    those over the clean pool and table."""
    lo, hi, MB = LATENT_BANDS["lo > 0"]
    q, c_pool, r_pool, tables, _, _ = _latent_inputs(len(hi), MB, seed=4)
    slot = np.arange(MB)[None, :]
    live = (slot * BLOCK < np.asarray(hi)[:, None]) & ((slot + 1) * BLOCK > np.asarray(lo)[:, None])
    NB = c_pool.shape[1]
    dead = np.setdiff1d(np.arange(NB), tables[live])
    poisoned = np.where(live, tables, np.random.default_rng(0).choice([-7, NB, NB + 1000], tables.shape))

    def poison(a):
        return jnp.full_like(a, jnp.nan).at[1].set(a[1].at[dead].set(jnp.nan))

    got = _simulated_partials(q[:, 0], poison(c_pool), poison(r_pool), poisoned, lo, hi, 1, 0.1)
    assert all(np.isfinite(np.asarray(x)).all() for x in got)
    _assert_partials_equal(got, _band_partials(q[:, 0], c_pool, r_pool, tables, lo, hi, 1, 0.1), hi, lo)


def test_the_engine_reports_the_latent_schedule(monkeypatch):
    """stats()["attention"]["decode_tile"] on the latent path is the schedule
    the kernel runs, from the engine's own pool (16-token blocks of f32 rows
    of 128 | 128): asked as a TPU would answer, since the CPU takes no
    kernel; nothing is traced."""
    import dataclasses

    cfg = dataclasses.replace(TransformerConfig.tiny_latent_moe(), kv_lora_rank=128)
    eng = LLMEngine(cfg, init_params(jax.random.PRNGKey(0), cfg), slots=1, max_seq_len=240, warmup=False)
    try:
        assert "decode_tile" not in eng.stats()["attention"]
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        paths = eng._attention_paths()
        assert paths["decode"] == "pallas_mla_paged"
        want = A.mla_decode_schedule(eng.kv.block, 128, 128, jnp.float32, eng.kv.table_width)
        assert paths["decode_tile"] == want.report()
        assert want.in_flight >= 2 and paths["decode_tile"]["walk"] == "across lanes"
    finally:
        eng.close()


def test_the_latent_kernel_says_why_not():
    assert A.mla_kernel_why_not(512, 128, 16, interpret=True) == ""
    assert "128-lane" in A.mla_kernel_why_not(32, 128, 16, interpret=True)
    assert "not tpu" in A.mla_kernel_why_not(512, 128, 16)


# -- the routed FFN -------------------------------------------------------------------


ROUTED = TransformerConfig(
    d_model=128, n_experts=8, moe_top_k=2, moe_score="sigmoid", moe_norm_topk=True, moe_scale=1.8,
    n_shared_experts=1, moe_d_ff=256, act="silu", dtype=jnp.float32)


def _routed_leaves(cfg, key, *, int8=True):
    E, d, fe = cfg.n_experts, cfg.d_model, cfg.moe_d_ff or cfg.d_ff
    ks = jax.random.split(key, 8)

    def stack(k, shape):
        w = jax.random.normal(k, shape, jnp.float32) / shape[-2] ** 0.5
        if not int8:
            return w
        q = jax.random.randint(k, shape, -127, 128, jnp.int8)
        return QTensor(q=q, s=jnp.full(shape[:-2] + (1, shape[-1]), 1.0 / (73 * shape[-2] ** 0.5), jnp.float32))

    lp = {"w_router": jax.random.normal(ks[0], (d, E)) / d ** 0.5,
          "w_gate": stack(ks[2], (E, d, fe)), "w_up": stack(ks[3], (E, d, fe)), "w_down": stack(ks[4], (E, fe, d))}
    if cfg.moe_score == "sigmoid":
        lp["router_bias"] = 0.01 * jax.random.normal(ks[1], (E,))
    if cfg.n_shared_experts:
        lp.update(ws_gate=stack(ks[5], (d, fe)), ws_up=stack(ks[6], (d, fe)), ws_down=stack(ks[7], (fe, d)))
    return lp


def _real(w):
    return w.q.astype(jnp.float32) * w.s if isinstance(w, QTensor) else w


def _per_token_loop(cfg, h, lp):
    """The reference's way: a token at a time, an expert at a time."""
    logits = np.asarray(h, np.float64) @ np.asarray(lp["w_router"], np.float64)
    scores = 1 / (1 + np.exp(-logits)) if cfg.moe_score == "sigmoid" else np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    choose = scores + (np.asarray(lp["router_bias"], np.float64) if "router_bias" in lp else 0.0)
    act = {"silu": lambda x: x / (1 + np.exp(-x)), "gelu": lambda x: np.asarray(jax.nn.gelu(x))}[cfg.act]

    def ffn(x, g, u, dn):
        return (act(x @ g) * (x @ u)) @ dn

    wg, wu, wd = (np.asarray(_real(lp[n]), np.float64) for n in ("w_gate", "w_up", "w_down"))
    out = np.zeros(h.shape, np.float64)
    for t in range(h.shape[0]):
        chosen = np.argsort(-choose[t], kind="stable")[: cfg.moe_top_k]
        w = scores[t, chosen]
        if cfg.moe_norm_topk:
            w = w / (w.sum() + 1e-20)
        x = np.asarray(h[t], np.float64)
        for e, we in zip(chosen, w * cfg.moe_scale):
            out[t] += we * ffn(x, wg[e], wu[e], wd[e])
        if "ws_gate" in lp:
            out[t] += ffn(x, *(np.asarray(_real(lp[n]), np.float64) for n in ("ws_gate", "ws_up", "ws_down")))
    return out


@pytest.mark.parametrize("kernel", ["ragged_dot", "pallas_interpret"])
def test_the_routed_ffn_equals_a_per_token_loop_and_drops_nothing(kernel):
    lp = _routed_leaves(ROUTED, jax.random.PRNGKey(0))
    h = jax.random.normal(jax.random.PRNGKey(9), (64, 128), jnp.float32)
    kw = dict(use_kernel=True, interpret=True) if kernel == "pallas_interpret" else dict(use_kernel=False)
    y, counts = M.routed_ffn(ROUTED, h, lp, qmm, **kw)
    assert np.abs(np.asarray(y) - _per_token_loop(ROUTED, h, lp)).max() < EXACT
    assert int(counts.sum()) == 64 * 2  # dropless: every pair was given a row


def test_a_tokens_output_does_not_change_with_its_batch_neighbours():
    """T = 1 against T = 64, and against a batch whose pairs ALL land on the same
    two experts (a capacity of 1.25 would have dropped most of them)."""
    lp = _routed_leaves(ROUTED, jax.random.PRNGKey(0))
    h = jax.random.normal(jax.random.PRNGKey(9), (64, 128), jnp.float32)
    full, _ = M.routed_ffn(ROUTED, h, lp, qmm)
    for t in (0, 17, 63):
        alone, counts = M.routed_ffn(ROUTED, h[t : t + 1], lp, qmm)
        assert float(jnp.abs(alone[0] - full[t]).max()) < 1e-5 and int(counts.sum()) == 2
    piled = dict(lp, router_bias=jnp.zeros((8,)).at[jnp.asarray([2, 5])].set(10.0))
    y, counts = M.routed_ffn(ROUTED, h, piled, qmm)
    assert counts.tolist() == [0, 0, 64, 0, 0, 64, 0, 0]
    assert np.abs(np.asarray(y) - _per_token_loop(ROUTED, h, piled)).max() < EXACT
    alone, _ = M.routed_ffn(ROUTED, h[5:6], piled, qmm)
    assert float(jnp.abs(alone[0] - y[5]).max()) < 1e-5


def test_the_router_multiplies_in_float32_at_the_highest_precision():
    """The stated precision of the router, pinned where it can be: its matmul
    takes float32 operands at Precision.HIGHEST whatever the activations'
    dtype. On the TPU the default float32 matmul is ONE bfloat16 pass, and no
    check of outputs can tell that from the bfloat16 activations around it
    (PERF.md section 6, PR 29), so the program's text is held to it here."""
    import dataclasses

    cfg = dataclasses.replace(ROUTED, dtype=jnp.bfloat16)
    lp = _routed_leaves(cfg, jax.random.PRNGKey(0))
    h = jnp.ones((4, cfg.d_model), jnp.bfloat16)
    dots = [e for e in jax.make_jaxpr(lambda h: M.route(cfg, h, lp))(h).jaxpr.eqns
            if e.primitive.name == "dot_general"]
    assert len(dots) == 1
    (dot,) = dots
    assert [str(v.aval.dtype) for v in dot.invars] == ["float32", "float32"]
    assert dot.params["precision"] == (jax.lax.Precision.HIGHEST, jax.lax.Precision.HIGHEST)
    assert str(dot.outvars[0].aval.dtype) == "float32"


def test_tiny_moe_goes_through_the_same_routed_ffn():
    """softmax, top-2, unnormalised, scale 1, no shared expert, plain float
    stacks: a configuration of the one routed FFN."""
    cfg = TransformerConfig.tiny_moe()
    lp = _routed_leaves(cfg, jax.random.PRNGKey(4), int8=False)
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 9, cfg.d_model), jnp.float32)
    y, stats = T._mlp_block(cfg, h, lp, qmm)
    want = _per_token_loop(cfg, h.reshape(18, -1), lp).reshape(h.shape)
    assert np.abs(np.asarray(y) - want).max() < EXACT
    assert stats.tolist()[0] == 18 * 2 and len(stats) == 2 + cfg.n_experts
    assert not hasattr(cfg, "moe_capacity")


# -- layer groups: the dense families' programs are what they were -------------------------

# tests/data/dense_programs_pr29.json: sha256 (first 16) of `jax.jit(f).lower(...).as_text()` for the
# model programs of four dense presets, plain and int8, as the PARENT of the PR that brought layer
# groups lowered them (PR 29: this file's `_dense_programs`, run in a checkout of that parent). PR 30
# re-pinned the 8 `decode_chunk_paged` entries, whose text changes by design (the pool stored flat and
# read whole at a layer's index); the 32 contiguous programs are PR 29's parent's, byte for byte.


def _dense_programs(name, quant):
    cfg = getattr(TransformerConfig, name)()
    params = init_params(jax.random.PRNGKey(0), cfg)
    if quant:
        params = quantize_params(params, cfg.dtype)
    L, b, cap, NB, K = cfg.n_layers, 3, 64, 12, 4
    width = cfg.n_kv_heads * cfg.head_dim  # the pool as stored: a row flat
    pool = T.KVCache(k=jnp.zeros((L, NB, BLOCK, width), cfg.dtype), v=jnp.zeros((L, NB, BLOCK, width), cfg.dtype),
                     length=jnp.zeros((b,), jnp.int32))
    dense = T.init_cache(cfg, b, cap)
    tok, rng = jnp.zeros((b,), jnp.int32), jax.random.PRNGKey(1)
    ones, temps, n8 = jnp.ones((b,), bool), jnp.zeros((b,)), jnp.full((b,), 8, jnp.int32)
    toks8, zeros = jnp.zeros((b, 8), jnp.int32), jnp.zeros((b,), jnp.int32)

    def sample(logits, temps, key):
        return jnp.argmax(logits, -1)

    return {
        "decode_chunk_paged": (lambda p, tok, pool, tables, act, temps, rng: T.decode_chunk_paged(
            p, cfg, tok, pool, None, tables, act, temps, rng, n_steps=K, sample_fn=sample, block=BLOCK,
            use_kernel=False), (params, tok, pool, jnp.zeros((b, cap // BLOCK), jnp.int32), ones, temps, rng)),
        "prefill_append": (lambda p, toks, cache, cur, n: T.prefill_append(p, cfg, toks, cache, cur, n, ring=0),
                           (params, toks8, dense, zeros, n8)),
        "decode_chunk": (lambda p, tok, cache, act, temps, rng: T.decode_chunk(
            p, cfg, tok, cache, act, temps, rng, n_steps=K, sample_fn=sample, ring=0),
            (params, tok, dense, ones, temps, rng)),
        "prefill": (lambda p, toks: T.prefill(p, cfg, toks, n8, cap), (params, toks8)),
        "verify_chunk": (lambda p, toks, cache, cur, n: T.verify_chunk(p, cfg, toks, cache, cur, n),
                         (params, toks8, dense, zeros, n8)),
    }


with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "dense_programs_pr29.json")) as _f:
    PINNED = json.load(_f)


@pytest.mark.parametrize("quant", [False, True], ids=["plain", "int8"])
@pytest.mark.parametrize("name", ["tiny", "tiny_llama", "tiny_mistral", "tiny_qwen2"])
def test_the_dense_families_programs_are_unchanged_by_the_layer_groups(name, quant):
    """One group is today's scan: the five model programs of a dense preset
    lower to the text they lowered to before the attention block, the cache's
    row and the layer scan were refactored under them."""
    for fn, (f, args) in _dense_programs(name, quant).items():
        text = jax.jit(f).lower(*args).as_text()
        key = f"{name}.{'int8' if quant else 'plain'}.{fn}"
        assert hashlib.sha256(text.encode()).hexdigest()[:16] == PINNED[key], key


def test_two_groups_scan_in_turn():
    cfg = TransformerConfig.tiny_latent_moe()
    assert cfg.group_sizes == (1, 2) and TransformerConfig.tiny_moe().group_sizes == (2,)
    params = init_params(jax.random.PRNGKey(0), cfg)
    dense, routed = T.layer_groups(params["layers"])
    assert "w_router" not in dense and dense["w_gate"].shape == (1, 64, 128)
    assert routed["w_gate"].shape == (2, 8, 64, 32) and routed["router_bias"].shape == (2, 8)
    quant = quantize_params(params, cfg.dtype)
    assert isinstance(quant["layers"][1]["wkv_b"], QTensor) and not isinstance(quant["layers"][1]["w_router"], QTensor)


# -- the cache's row comes from the config in one place -----------------------------------


def test_the_cache_row_is_described_once():
    latent, dense = TransformerConfig.tiny_latent_moe(), TransformerConfig.tiny_qwen2()
    assert row_shapes(latent) == ((1, 32), (1, 128)) and row_shapes(dense) == ((2, 16), (2, 16))
    kv = CacheManager(latent, 2, 96, 4, paged=True, block=16)
    pool, scales = kv.pool_arrays(jnp)
    # stored as a page is read: a row flat, [L, NB, B, heads * dim], and that is all pool_shapes says
    assert (pool.k.shape, pool.v.shape) == kv.pool_shapes() and scales is None
    assert pool.k.shape[2:] == (16, 32) and pool.v.shape[2:] == (16, 128)
    assert kv.row_bytes == 160 * 4 and kv.block_bytes == latent.n_layers * 16 * kv.row_bytes
    assert kv.stats()["row_bytes"] == kv.row_bytes
    qkv = CacheManager(dense, 2, 96, 4, paged=True, block=16)
    assert qkv.pool_shapes() == ((dense.n_layers, qkv.pool.n_blocks, 16, 2 * 16),) * 2
    assert qkv.row_bytes == 2 * 2 * 16 * 4 and qkv.block_bytes == 2 * dense.n_layers * 16 * 2 * 16 * 4
    with pytest.raises(ValueError, match="int8 KV pool is not supported with latent"):
        CacheManager(latent, 2, 96, 4, paged=True, block=16, kv_int8=True)


# -- what is left out is refused with a sentence ---------------------------------------------


REFUSED = {
    "tensor_parallel": (dict(mesh="a mesh"), "tensor or expert parallelism"),
    "contiguous_layout": (dict(kv_paged=False), "contiguous layout"),
    "wave_scheduler": (dict(chunked=False), "step_token_budget=0"),
    "speculative": (dict(speculative=True), "verify_chunk"),
    "constrained": (dict(constrained=True), "constrained decoding"),
    "lora": (dict(lora_slots=2), "LoRA adapters on the latent projections"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_what_the_latent_family_leaves_out_is_refused_with_a_sentence(case):
    change, words = REFUSED[case]
    base = dict(mesh=None, chunked=True, kv_paged=None, speculative=False, constrained=None, lora_slots=0)
    assert latent_refusal(**base) is None
    assert words in latent_refusal(**{**base, **change})


@pytest.mark.parametrize("kw,words", [
    (dict(kv_paged=False), "contiguous layout"), (dict(speculative=True), "verify_chunk"),
    (dict(constrained=True), "constrained decoding"), (dict(lora_slots=2), "LoRA adapters"),
    (dict(step_token_budget=0), "wave scheduler"),
])
def test_the_engine_refuses_at_build(kw, words):
    cfg = TransformerConfig.tiny_latent_moe()
    with pytest.raises(ValueError, match=words):
        LLMEngine(cfg, init_params(jax.random.PRNGKey(0), cfg), slots=2, max_seq_len=64, warmup=False, **kw)
    with pytest.raises(ValueError, match="no contiguous decode chunk"):
        T.decode_chunk(None, cfg, jnp.zeros((1,), jnp.int32), T.init_cache(cfg, 1, 8), None, None,
                       jax.random.PRNGKey(0), n_steps=1, sample_fn=None)


def test_a_fallback_of_the_grouped_matmul_on_the_tpu_is_a_degraded_program(monkeypatch):
    """Off the TPU `ragged_dot` is the path and is only named; on it the engine
    lists the fallback among the registry's degraded programs, which the
    benchmark counts (`degraded_programs`: the run is then not correct)."""
    from gofr_tpu.profiling import default_registry

    cfg = TransformerConfig.tiny_latent_moe()  # experts [64, 32]: not whole 128-lane tiles
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = LLMEngine(cfg, params, slots=2, max_seq_len=64, warmup=False, kv_label="moe-cpu")
    try:
        assert eng.stats()["moe"]["experts"].startswith("ragged_dot (backend cpu")
        assert not [d for d in default_registry().snapshot()["degraded"] if d["model"] == "moe-cpu"]
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert "128-lane tiles" in eng._moe_path()
        (d,) = [d for d in default_registry().snapshot()["degraded"] if d["model"] == "moe-cpu"]
        assert d["program"] == "moe_grouped_matmul" and "128-lane tiles" in d["reason"]
    finally:
        monkeypatch.undo()
        eng.close()


def test_two_groups_of_a_gqa_model_serve_and_refuse_what_was_written_for_one():
    """Leading dense layers are not latent attention's alone: a GQA model of
    two groups serves through the same indexed scan, and is refused LoRA slots
    (the adapter tables are stacked for one group) with a sentence."""
    import dataclasses

    cfg = dataclasses.replace(TransformerConfig.tiny_moe(), n_dense_layers=1)
    params = init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="two layer groups"):
        LLMEngine(cfg, params, slots=2, max_seq_len=64, warmup=False, lora_slots=2)
    eng = LLMEngine(cfg, params, slots=2, max_seq_len=64, warmup=False)
    try:
        assert len(eng.generate(list(range(1, 12)), max_new_tokens=5)) == 5
        assert eng.stats()["moe"]["layer_calls"] > 0
    finally:
        eng.close()


def test_a_request_is_served_by_default_settings():
    """No keyword of the engine names the family: the defaults serve it."""
    cfg = TransformerConfig.tiny_latent_moe()
    eng = LLMEngine(cfg, init_params(jax.random.PRNGKey(0), cfg), slots=2, max_seq_len=64, warmup=False)
    try:
        req = eng.submit(GenRequest(list(range(1, 20)), max_new_tokens=9))
        assert len(req.tokens()) == 9 and eng.stats()["kvcache"]["layout"] == "paged"
    finally:
        eng.close()


# -- the benchmark's configurations name whole families (tier-1 copy) ----------------------------


def _config_files():
    return sorted(fn[:-5] for fn in os.listdir(os.path.join(BENCH, "configs")) if fn.endswith(".json"))


@pytest.mark.parametrize("name", _config_files())
def test_a_configuration_names_a_whole_family_and_its_entry_agrees(name):
    """benchmarks/tests/test_run.py's test of the same name, where tier-1 runs
    it: a configuration names a `family` with the six names, BENCHMARK.json's
    entry says what the file says, and the family reads every key it needs of
    the model group and of the twin."""
    run = _bench("run")
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        cfg = json.load(f)
    assert os.path.isfile(os.path.join(BENCH, "families", cfg["family"] + ".py"))
    family = run.load_family(cfg)  # raises where one of the six names is missing
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = {c["name"]: c for c in json.load(f)["configs"]}[name]
    assert entry["file"] == f"benchmarks/configs/{name}.json" and entry["source"] == cfg["source"]
    assert sorted(entry["reduced"]) == sorted(cfg.get("reduced", []))
    assert set(cfg.get("published", {})) == set(entry["reduced"])
    assert cfg["deployment"] and isinstance(cfg["model"]["vocab_size"], int)
    for twin_model in (cfg["model"], cfg["rehearsal"]["model"]):
        assert family.least_step_seconds(twin_model, {"bf16_flops": 1e12, "int8_ops": 2e12}, prefill_contexts=[1, 2],
                                         decode_contexts=[3], prefill_int8=True)["seconds"] > 0
        assert family.decode_kv_read_bytes(twin_model, [3, 4]) > 0

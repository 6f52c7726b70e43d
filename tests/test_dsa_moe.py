"""DeepSeek-V3.2's block on the normal path, on the CPU at its float32 twin
with seeded weights: latent attention with YaRN rope and a lightning indexer
that picks each query's top `index_topk` rows, group-limited routing over a
held share of the experts, through the paged latent cache whose rope array
carries the index key.

Compared on LOGITS against the benchmark's plain reference (expanded form,
float32, the indexer's full score matrix and an exact top-k per query;
benchmarks/dsa_moe_reference.py), which shares no code with the program. The
twin (benchmarks/configs/deepseek-v3.2.json, `rehearsal`) has a top-8 over
contexts of ~40, so the selection engages. `EXACT` and the weight-only prompt
chunks are test_latent_moe.py's, for its reasons.
"""

import dataclasses
import json
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.kvcache import CacheManager, row_shapes
from gofr_tpu.kvcache.paged import gather_blocks_host, gather_slots, scatter_rows
from gofr_tpu.llm import GenRequest, LLMEngine
from gofr_tpu.models import TransformerConfig, init_params
from gofr_tpu.models import moe as M
from gofr_tpu.models import transformer as T
from gofr_tpu.models.quant import qmm
from gofr_tpu.ops import attention as A
from gofr_tpu.ops import rope as RP

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
EXACT = 2e-4
SEED = 2**31 + 40
BLOCK = 16


def _bench(name):
    sys.path[:0] = [BENCH]
    try:
        return __import__(name)
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def twin():
    """(model group, family module, program config, program params) of the
    DeepSeek-V3.2 configuration's CPU twin."""
    run = _bench("run")
    with open(os.path.join(BENCH, "configs", "deepseek-v3.2.json")) as f:
        cfg_file = json.load(f)
    model = cfg_file["rehearsal"]["model"]
    family = run.load_family(cfg_file)
    return model, family, family.program_config(model), family.program_params(model, SEED)


@pytest.fixture()
def weight_only(monkeypatch):
    monkeypatch.setattr(T, "qmm_a8", qmm)


def _tokens(n, s, vocab=512, seed=0):
    return np.random.default_rng(seed).integers(1, vocab, (n, s)).astype(np.int32)


def _forward(cfg, params, toks):
    pos = jnp.broadcast_to(jnp.arange(toks.shape[1]), toks.shape)
    return np.asarray(jax.jit(lambda p, t: T.transformer_forward(p, cfg, t, pos)[0])(params, jnp.asarray(toks)))


# -- the whole-sequence forward pass -----------------------------------------------------


def test_the_twin_is_the_published_block(twin):
    _model, _family, cfg, _params = twin
    assert cfg.sparse and cfg.latent and cfg.index_topk == 8 and cfg.moe_groups == 2
    assert cfg.moe_held_experts == 4 and cfg.n_experts == 8 and cfg.rope_scaling[0] == 40.0


def test_absorbed_sparse_equals_expanded(twin, weight_only):
    """40 tokens against a top-8: every query past the eighth attends only what
    the indexer chose. The program (absorbed query, each query's chosen rows
    gathered) gives the reference's logits (keys and values of every head
    written out, the indexer's full score matrix, top-k per query)."""
    model, family, cfg, params = twin
    toks = _tokens(2, 40)
    got = _forward(cfg, params, toks)
    want = np.asarray(family.forward_logits(model, SEED, toks))
    assert np.abs(got - want).max() < EXACT
    assert want.std() > 0.5


def test_the_selection_is_load_bearing(twin):
    """A reference that attends every causal row reads other logits past the
    eighth position: the test above would see a program that never selected."""
    model, family, _cfg, _params = twin
    toks = _tokens(1, 40)
    want = np.asarray(family.forward_logits(model, SEED, toks))
    plain = np.asarray(family.forward_logits({**model, "index_topk": 64}, SEED, toks))
    assert np.abs(plain[0, :8] - want[0, :8]).max() < 1e-5
    assert np.abs(plain[0, 12:] - want[0, 12:]).max() > 1e-2


def test_a_top_k_of_the_whole_context_is_plain_latent_attention(twin):
    """index_topk >= the context: the layer is MLA, token for token the
    program without an indexer on the same weights."""
    _model, _family, cfg, params = twin
    toks = _tokens(2, 40, seed=2)
    wide = dataclasses.replace(cfg, index_topk=40)
    plain = dataclasses.replace(cfg, index_heads=0, index_head_dim=0, index_topk=0)
    drop = ("wq_idx", "wk_idx", "w_idx", "k_idx_norm", "k_idx_bias")
    bare = {**params, "layers": tuple({k: v for k, v in g.items() if k not in drop} for g in params["layers"])}
    np.testing.assert_array_equal(_forward(wide, params, toks), _forward(plain, bare, toks))


# -- prefill, then decode, through the paged latent cache --------------------------------------


def _forced(forced):
    def sample(logits, temps, key, state):
        i, buf = state
        return forced[i], (i + 1, buf.at[i].set(logits))

    return sample


def _serve_paged(cfg, params, seqs, prompt_lens, tables, *, chunk=8, K=4, shared=0, use_kernel=False):
    """test_latent_moe._serve_paged: prompt chunks through gather_slots ->
    prefill_append -> scatter_rows (lane i from cursor `shared` where its
    table shares lane 0's blocks), then forced decode chunks of K through
    decode_chunk_paged. Returns {(lane, position): logits}."""
    b, total = seqs.shape
    (k_row, v_row), L = row_shapes(cfg), cfg.n_layers
    NB = int(tables.max()) + 1
    pool = T.KVCache(k=jnp.zeros((L, NB, BLOCK, math.prod(k_row)), cfg.dtype),
                     v=jnp.zeros((L, NB, BLOCK, math.prod(v_row)), cfg.dtype), length=jnp.zeros((b,), jnp.int32))
    tables = jnp.asarray(tables)
    out = {}
    cursors = np.array([0] + [shared] * (b - 1)) if shared else np.zeros((b,), np.int64)
    while (cursors < prompt_lens).any():
        n_new = np.minimum(chunk, prompt_lens - cursors)
        if cursors[0] < shared:
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
    return out, pool


def test_prefill_then_decode_through_the_paged_latent_cache(twin, weight_only):
    """Prompts of 21 and 37 tokens in chunks of 8, then 20 forced decode
    tokens in chunks of 4, blocks scattered over the pool: the selection
    engages in both (a chunk's query chooses among the view's rows, a decode
    step's among the pool's and the chunk buffer's). Every logit is the
    reference's."""
    model, family, cfg, params = twin
    seqs = _tokens(2, 64, seed=3)
    tables = np.random.default_rng(1).permutation(8).reshape(2, 4).astype(np.int32)
    got, _ = _serve_paged(cfg, params, seqs, np.array([21, 37]), tables)
    want = np.asarray(family.forward_logits(model, SEED, seqs))
    assert {(0, 20), (1, 36), (0, 21), (0, 40), (1, 37), (1, 56)} <= set(got)
    worst = max(np.abs(lg - want[i, p]).max() for (i, p), lg in got.items())
    assert worst < EXACT, worst


def test_a_shared_prefix_carries_its_index_keys(twin, weight_only):
    """Radix reuse as the engine seeds it: lane 1's table names lane 0's first
    two blocks and its prefill starts at cursor 32, so its queries choose
    among index keys that lane 0 wrote. Its logits are those of its whole
    sequence served cold; and the blocks that leave the pool (a session's
    spill, gather_blocks_host) hold the index keys beside the rope keys."""
    model, family, cfg, params = twin
    seqs = _tokens(2, 60, seed=5)
    seqs[1, :32] = seqs[0, :32]
    tables = np.array([[3, 5, 0, 6], [3, 5, 2, 1]], np.int32)
    got, pool = _serve_paged(cfg, params, seqs, np.array([40, 44]), tables, shared=32)
    want = np.asarray(family.forward_logits(model, SEED, seqs))
    worst = max(np.abs(lg - want[i, p]).max() for (i, p), lg in got.items())
    assert (1, 43) in got and (1, 55) in got and worst < EXACT, worst
    k, v, _ = gather_blocks_host(pool.k, pool.v, [3, 5], rows=row_shapes(cfg))
    R = A.latent_rope_width(cfg.qk_rope_head_dim)
    assert v.shape[-1] == R + cfg.index_head_dim and np.abs(v[..., R:]).max() > 0.1


def test_the_engine_serves_it_and_a_radix_hit_equals_cold(twin):
    """Through LLMEngine as the benchmark drives it (W8A8 prompt chunks): a
    prompt whose first 32 tokens are a published prefix gives the greedy
    tokens it gives served cold, and the sparse rows are counted."""
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
        att = st["attention"]
        assert att["decode"].startswith("xla_gather_sparse") and att["prefill"][8].startswith("xla_sparse_latent")
        sp = att["sparse"]
        assert sp["index_topk"] == 8 and 0 < sp["rows_selected"] < sp["rows_in_context"]
        ctx = [c for r in st["step_log"]["records"] for c in r[st["step_log"]["fields"].index("decode_ctx")]]
        rows = [np.arange(c + 1, c + n + 1) for c, n in ctx]
        assert sp["rows_in_context"] == cfg.n_layers * sum(int(r.sum()) for r in rows)
        assert sp["rows_selected"] == cfg.n_layers * sum(int(np.minimum(r, 8).sum()) for r in rows)
        assert st["kvcache"]["row_bytes"] == (32 + 128 + 16) * 4
    finally:
        eng.close()


# -- the sparse decode kernels ---------------------------------------------------------------


def _sparse_inputs(b, MB, hq=5, C=128, R=128, di=128, ih=16, seed=1):
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    NB = b * MB
    c_pool = jax.random.normal(keys[0], (NB, BLOCK, C), jnp.float32)
    v_pool = jnp.concatenate([
        jnp.pad(jax.random.normal(keys[1], (NB, BLOCK, 8), jnp.float32), ((0, 0), (0, 0), (0, R - 8))),
        jax.random.normal(keys[5], (NB, BLOCK, di), jnp.float32)], axis=-1)
    c_pool, v_pool = (jnp.stack([jnp.zeros_like(a), a]) for a in (c_pool, v_pool))
    sq = A.SparseQuery(
        jax.random.normal(keys[2], (b, 1, hq, C + R), jnp.float32),
        jax.random.normal(keys[6], (b, 1, ih, di), jnp.float32),
        jax.random.normal(keys[7], (b, 1, ih), jnp.float32))
    tables = np.random.default_rng(seed).permutation(NB).reshape(b, MB).astype(np.int32)
    c_buf = jax.random.normal(keys[3], (b, 4, 1, C), jnp.float32)
    v_buf = jnp.concatenate([
        jnp.pad(jax.random.normal(keys[4], (b, 4, 1, 8), jnp.float32), ((0, 0),) * 3 + ((0, R - 8),)),
        jax.random.normal(keys[5], (b, 4, 1, di), jnp.float32)], axis=-1)
    return sq, c_pool, v_pool, tables, c_buf, v_buf


@pytest.mark.parametrize("lengths,MB", [([0, 37, 190], 12), ([150, 0, 70, 129], 12), ([300, 29, 310], 20)])
def test_the_index_kernel_in_interpret_mode_equals_the_dense_gather(lengths, MB):
    """dsa_index_decode's scores of every row of the lanes' bands, its page
    copies simulated, against the gathered index keys scored in XLA."""
    from jax.experimental.pallas import tpu as pltpu

    b = len(lengths)
    sq, _c, v_pool, tables, _cb, _vb = _sparse_inputs(b, MB)
    hi = jnp.asarray(lengths, jnp.int32)
    got = A._dsa_index_call(sq.q_idx[:, 0], sq.w_idx[:, 0], v_pool, jnp.asarray(tables), hi, 1,
                            rope=128, interpret=pltpu.InterpretParams())
    keys = A.take_pages(v_pool, 1, jnp.asarray(tables)).reshape(b, MB * BLOCK, -1)[..., 128:]
    want = A.dsa_index_scores(sq.q_idx, sq.w_idx, keys)[:, 0]
    live = np.arange(MB * BLOCK)[None, :] < np.asarray(lengths)[:, None]
    got, want = np.asarray(got), np.asarray(want)
    assert (got[~live] == A.NEG_INF).all()
    np.testing.assert_allclose(got[live], want[live], rtol=1e-5, atol=1e-4)


def test_the_prefill_index_kernel_in_interpret_mode_equals_xla():
    """dsa_index_prefill's scores of a prompt chunk's queries against every
    index key of the view (a key count that is no whole block), and the whole
    prompt-chunk attention through it against the XLA scores."""
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    b, c, ih, di, n, C, R = 2, 32, 8, 128, 700, 32, 128
    q_idx, w_idx = jax.random.normal(ks[0], (b, c, ih, di)), jax.random.normal(ks[1], (b, c, ih))
    keys = jax.random.normal(ks[2], (b, n, di))
    got = A._dsa_index_prefill_call(q_idx, w_idx, keys, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(A.dsa_index_scores(q_idx, w_idx, keys)), rtol=1e-5, atol=1e-4)
    sq = A.SparseQuery(jax.random.normal(ks[3], (b, c, 4, C + R)), q_idx, w_idx)
    cc, vc = jax.random.normal(ks[4], (b, n, 1, C)), jax.random.normal(ks[5], (b, n, 1, R + di))
    cur = jnp.asarray([100, 600], jnp.int32)
    kw = dict(scale=0.1, topk=64, rope=R)
    kernel = A.dsa_chunk_prefill_attention(sq, cc, vc, cur, **kw, use_kernel=True, interpret=True)
    xla = A.dsa_chunk_prefill_attention(sq, cc, vc, cur, **kw, use_kernel=False)
    assert float(jnp.abs(kernel - xla).max()) < 1e-4
    assert A.dsa_prefill_why_not(128, 64, 64, interpret=True) == ""
    assert "blocks of 16" in A.dsa_prefill_why_not(128, 8, 64, interpret=True)


def test_the_sparse_decode_with_kernels_equals_the_gather_path():
    """The whole chunk-decode attention: both kernels (interpret mode) against
    the XLA path, with a top-k of 128 over contexts past and under it."""
    b, MB = 3, 12
    sq, c_pool, v_pool, tables, c_buf, v_buf = _sparse_inputs(b, MB, seed=3)
    args = (sq, c_pool, v_pool, jnp.asarray(tables), c_buf, v_buf, jnp.asarray([100, 190, 0], jnp.int32), jnp.int32(2))
    kw = dict(scale=0.1, layer=1, topk=128, rope=128)
    kernel = A.dsa_paged_chunk_decode_attention(*args, **kw, use_kernel=True, interpret=True)
    gather = A.dsa_paged_chunk_decode_attention(*args, **kw, use_kernel=False)
    assert kernel.shape == (b, 1, 5, 128) and float(jnp.abs(kernel - gather).max()) < 1e-4


def test_the_sparse_kernels_say_why_not():
    assert A.dsa_kernel_why_not(512, 128, 128, 16, 2048, interpret=True) == ""
    assert "index key" in A.dsa_kernel_why_not(512, 128, 64, 16, 2048, interpret=True)
    assert "chosen rows" in A.dsa_kernel_why_not(512, 128, 128, 16, 200, interpret=True)
    assert "not tpu" in A.dsa_kernel_why_not(512, 128, 128, 16, 2048)


def test_the_engine_reports_the_sparse_schedule(twin, monkeypatch):
    """stats()["attention"] on a TPU: the kernels' path and the index kernel's
    schedule over the rope array's pages (asked as a TPU would answer; the
    twin's widths are raised to whole lanes, nothing is traced)."""
    _model, _family, cfg, _params = twin
    wide = dataclasses.replace(cfg, kv_lora_rank=128, index_head_dim=128, index_topk=128)
    eng = LLMEngine(wide, init_params(jax.random.PRNGKey(0), wide), slots=1, max_seq_len=240, warmup=False)
    try:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        paths = eng._sparse_attention_paths()
        assert paths["decode"] == "pallas_dsa_sparse (dsa_index_decode, top-128, mla_sparse_decode)"
        want = A.mla_decode_schedule(eng.kv.block, 256, 0, wide.dtype, eng.kv.table_width)
        assert paths["decode_tile"] == want.report() and paths["pool_operand"] == "whole stack"
    finally:
        eng.close()


# -- routing ---------------------------------------------------------------------------------


def test_group_limited_choice_by_hand():
    """8 experts in 4 groups of 2, the best 2 groups eligible. Group scores
    (the sum of a group's two): g0 0.3, g1 0.9, g2 0.95, g3 0.6, so g2 and g1
    stay: experts 2, 3, 4, 5. The top 3 is then 2, 4, 5: expert 5 (0.45) is
    chosen over experts 6 and 7 (0.3 each), whose group is out, and expert 3
    (0.0) stays eligible where expert 1 (0.2) does not."""
    c = jnp.asarray([[0.1, 0.2, 0.9, 0.0, 0.5, 0.45, 0.3, 0.3]])
    got = np.asarray(M.group_limited(c, 4, 2))
    assert np.isneginf(got[0, [0, 1, 6, 7]]).all() and (got[0, [2, 3, 4, 5]] == np.asarray(c)[0, [2, 3, 4, 5]]).all()
    assert jax.lax.top_k(got, 3)[1].tolist() == [[2, 4, 5]]
    # one group of all eight is no limit: route() does not call it, and it would change nothing
    np.testing.assert_array_equal(np.asarray(M.group_limited(c, 1, 1)), np.asarray(c))


def test_route_is_the_identity_at_one_group():
    """route over groups of one eligible group of all: the plain top-k, the same program."""
    cfg = TransformerConfig.tiny_latent_moe()
    lp = {"w_router": jax.random.normal(jax.random.PRNGKey(0), (64, 8)) / 8.0,
          "router_bias": 0.01 * jax.random.normal(jax.random.PRNGKey(1), (8,))}
    h = jax.random.normal(jax.random.PRNGKey(2), (16, 64))
    one = dataclasses.replace(cfg, moe_groups=1, moe_topk_groups=1)
    assert jax.jit(lambda h: M.route(cfg, h, lp)).lower(h).as_text() == \
        jax.jit(lambda h: M.route(one, h, lp)).lower(h).as_text()
    grouped = dataclasses.replace(cfg, moe_groups=4, moe_topk_groups=4)  # every group eligible
    for a, b in zip(M.route(grouped, h, lp), M.route(cfg, h, lp)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_shares_add_up_to_the_uncut_layer(twin):
    """The guide's section 4 test under group-limited routing: the reference's
    routed parts of two shares (experts 0-3 and 4-7 of the twin's 8) add to
    its uncut layer's, and so do the program's: two routed FFNs holding a
    share each, the shared expert counted once, give the one that holds all
    8. The router scores and chooses over all of them, in every group, in
    each share."""
    model, _family, cfg, _params = twin
    W, REF = _bench("dsa_moe_weights"), _bench("dsa_moe_reference")
    uncut = {**model, "n_routed_experts": 8}
    shares = [{**model, "first_expert_held": first} for first in (0, 4)]
    h = jax.random.normal(jax.random.PRNGKey(7), (24, model["hidden_size"]), jnp.float32)
    key = W.layer_keys(W.base_key(SEED), model)[1]
    with jax.default_matmul_precision("highest"):
        parts = [np.asarray(REF.routed_part(m, W.layer_leaves(m, key, True), h)) for m in shares]
        whole = np.asarray(REF.routed_part(uncut, W.layer_leaves(uncut, key, True), h))
    np.testing.assert_allclose(parts[0] + parts[1], whole, atol=1e-5)

    def layer(m):  # the program's leaves of the first routed layer
        return jax.tree.map(lambda a: a[0], _family_params(m)["layers"][1])

    full = layer(uncut)
    y_full = M.routed_ffn(dataclasses.replace(cfg, moe_held_experts=0), h, full, qmm)[0]
    ys = [M.routed_ffn(dataclasses.replace(cfg, moe_first_expert=m["first_expert_held"], moe_held_experts=4),
                       h, layer(m), qmm)[0] for m in shares]
    shared = qmm(jax.nn.silu(qmm(h, full["ws_gate"])) * qmm(h, full["ws_up"]), full["ws_down"])
    np.testing.assert_allclose(np.asarray(ys[0] + ys[1] - shared), np.asarray(y_full), atol=1e-4)


def _family_params(model):
    run = _bench("run")
    with open(os.path.join(BENCH, "configs", "deepseek-v3.2.json")) as f:
        family = run.load_family(json.load(f))
    return family.program_params(model, SEED)


# -- YaRN ----------------------------------------------------------------------------------


def test_yarn_frequencies_and_mscale_by_hand():
    """factor 40 over 4,096 positions, beta_fast 32, beta_slow 1, 64 rope
    columns of base 10,000: the correction dims are floor(64 ln(4096 / (32 2pi))
    / (2 ln 10^4)) = floor(10.47) = 10 and ceil(64 ln(4096 / 2pi) / (2 ln 10^4))
    = ceil(22.51) = 23, so pairs 0..10 keep their frequency, 23..31 are divided
    by 40 and pair i between blends by (i - 10) / 13. The score scale is
    192^-1/2 (0.1 ln 40 + 1)^2 = 0.0721688 x 1.3688879^2 = 0.1352338."""
    scaling = (40.0, 4096, 32.0, 1.0, 1.0, 1.0)
    got = np.asarray(RP.rope_frequencies(64, 10_000.0, scaling), np.float64)
    f = 1.0 / 10_000.0 ** (np.arange(0, 64, 2) / 64)
    assert np.allclose(got[:11], f[:11], rtol=1e-6) and np.allclose(got[23:], f[23:] / 40, rtol=1e-6)
    ramp = (16 - 10) / 13
    assert math.isclose(got[16], f[16] / 40 * ramp + f[16] * (1 - ramp), rel_tol=1e-6)
    assert math.isclose(RP.yarn_mscale(40.0, 1.0), 1.3688879454113936, rel_tol=1e-12)
    cfg = dataclasses.replace(TransformerConfig.tiny_latent_moe(), qk_nope_head_dim=128, qk_rope_head_dim=64,
                              rope_scaling=scaling)
    assert math.isclose(T._latent_scale(cfg), 0.1352338, rel_tol=1e-6)
    # no scaling: today's frequencies, and the reference's YaRN is the program's
    np.testing.assert_array_equal(np.asarray(RP.rope_frequencies(64, 10_000.0)), np.asarray(
        1.0 / (10_000.0 ** (jnp.arange(0, 64, 2, dtype=jnp.float32) / 64))))
    REF = _bench("dsa_moe_reference")
    inv, trig, m2 = REF.yarn({"qk_rope_head_dim": 64, "rope_theta": 10000, "rope_scaling": {
        "factor": 40, "original_max_position_embeddings": 4096, "beta_fast": 32, "beta_slow": 1,
        "mscale": 1, "mscale_all_dim": 1, "type": "yarn"}})
    np.testing.assert_allclose(np.asarray(inv), got, rtol=1e-6)
    assert trig == 1.0 and math.isclose(m2, 1.3688879454113936**2, rel_tol=1e-12)


# -- what is refused, with a sentence ----------------------------------------------------------


REFUSED = {
    "an indexer without latent attention": (
        lambda: dataclasses.replace(TransformerConfig.tiny_moe(), index_heads=4, index_head_dim=16, index_topk=8),
        "latent attention"),
    "an indexer head no wider than its rope part": (
        lambda: dataclasses.replace(TransformerConfig.tiny_dsa_moe(), index_head_dim=8), "wider than the rope part"),
    "groups that do not divide the experts": (
        lambda: dataclasses.replace(TransformerConfig.tiny_dsa_moe(), moe_groups=3), "cannot give a top-2"),
    "too few eligible experts": (
        lambda: dataclasses.replace(TransformerConfig.tiny_dsa_moe(), moe_groups=8, moe_topk_groups=1),
        "cannot give a top-2"),
    "rope scaling that is not YaRN's six numbers": (
        lambda: dataclasses.replace(TransformerConfig.tiny_dsa_moe(), rope_scaling=(40.0,)), "YaRN"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_what_the_config_cannot_be_is_refused_with_a_sentence(case):
    build, words = REFUSED[case]
    with pytest.raises(ValueError, match=words):
        build()


def test_a_program_without_the_fields_is_refused_at_once(twin, monkeypatch):
    """The family's program_config against an older TransformerConfig (the
    parent of the PR that brought it): a sentence, at once."""
    model, family, _cfg, _params = twin
    import gofr_tpu.models as models

    @dataclasses.dataclass(frozen=True)
    class Older:
        vocab_size: int = 0

    monkeypatch.setattr(models, "TransformerConfig", Older)
    with pytest.raises(SystemExit, match="lightning indexer"):
        family.program_config(model)


def test_the_engine_refuses_what_the_latent_family_leaves_out(twin):
    _model, _family, cfg, params = twin
    with pytest.raises(ValueError, match="verify_chunk"):
        LLMEngine(cfg, params, slots=2, max_seq_len=64, warmup=False, speculative=True)
    with pytest.raises(ValueError, match="int8 KV pool"):
        CacheManager(cfg, 2, 96, 4, paged=True, block=16, kv_int8=True)


# -- the docs cell's scheduling ---------------------------------------------------------------


def test_a_prefix_hit_is_not_queued_behind_cold_prompts(twin):
    """A prompt the radix tree served has only its unshared rows left, and
    they go out ahead of cold prompts' rows: queued behind three cold
    prompts of 13 rows each (two rows a step), the hit's four tokens ride
    the first step, where FIFO by request would hold them until every cold
    row had gone (the second requests of the docs cell's early clients,
    waiting out the whole cold ramp)."""
    _model, _family, cfg, params = twin
    eng = LLMEngine(cfg, params, slots=4, max_seq_len=128, prefill_chunk=8, prefill_buckets=(8,),
                    step_token_budget=16, decode_chunk=4, warmup=False, prefix_cache_mb=1)
    try:
        doc = list(range(1, 33))
        eng.generate(doc + [90], max_new_tokens=2)  # publishes the document's blocks
        colds = [_tokens(1, 100, seed=s)[0].tolist() for s in (11, 12, 13)]
        steps = eng.stats()["steps"]
        reqs = [eng.submit(GenRequest(p, max_new_tokens=2)) for p in colds]
        deadline = time.time() + 60
        while eng.stats()["steps"] < steps + 2 and time.time() < deadline:  # the cold rows are under way
            time.sleep(0.01)
        hit = eng.submit(GenRequest(doc + [91, 92, 93, 94], max_new_tokens=2))
        for r in reqs + [hit]:
            r.tokens()
        log = eng.stats()["request_log"]
        rec = {r[log["fields"].index("id")]: dict(zip(log["fields"], r)) for r in log["records"]}
        h = rec[hit.id]
        assert h["cached"] == 32 and h["rows"] == 1
        assert all(h["seq_last_row"] < rec[r.id]["seq_last_row"] for r in reqs), (h, [rec[r.id] for r in reqs])
    finally:
        eng.close()


def _log(eng):
    log = eng.stats()["request_log"]
    return {r[log["fields"].index("id")]: dict(zip(log["fields"], r)) for r in log["records"]}


def test_clients_asking_one_new_document_prefill_it_once(twin):
    """Four requests that open with one 48-token document, sent together to
    an engine that has never seen it: the first prefills the document and
    the other three wait for its blocks to be published, then seed them
    from the radix tree (the docs cell's cold ramp: sixteen clients, two
    documents). Each gets the tokens it gets served alone."""
    _model, _family, cfg, params = twin
    kw = dict(slots=4, max_seq_len=128, prefill_chunk=8, prefill_buckets=(8,), step_token_budget=16,
              decode_chunk=4, warmup=False)
    doc = _tokens(1, 48, seed=21)[0].tolist()
    prompts = [doc + [100 + i, 200 + i, 300 + i] for i in range(4)]
    alone = LLMEngine(cfg, params, **kw)
    try:
        want = [alone.generate(p, max_new_tokens=3) for p in prompts]
    finally:
        alone.close()
    eng = LLMEngine(cfg, params, prefix_cache_mb=1, **kw)
    try:
        reqs = [eng.submit(GenRequest(p, max_new_tokens=3)) for p in prompts]
        assert [r.tokens() for r in reqs] == want
        rec = _log(eng)
        assert sorted(rec[r.id]["cached"] for r in reqs) == [0, 48, 48, 48]
        assert sum(rec[r.id]["rows"] for r in reqs) == 7 + 3  # the document's 6 rows and a tail once, 1 row each after
    finally:
        eng.close()


def test_a_prompt_sharing_little_with_a_long_one_does_not_wait(twin):
    """A prompt that shares one block with a 96-token prompt still in
    prefill goes out beside it: waiting would cost it more rows than the
    block saves."""
    _model, _family, cfg, params = twin
    eng = LLMEngine(cfg, params, slots=2, max_seq_len=128, prefill_chunk=8, prefill_buckets=(8,),
                    step_token_budget=16, decode_chunk=4, warmup=False, prefix_cache_mb=1)
    try:
        long_ = _tokens(1, 96, seed=31)[0].tolist()
        short = long_[:16] + _tokens(1, 24, seed=32)[0].tolist()
        a = eng.submit(GenRequest(long_, max_new_tokens=2))
        b = eng.submit(GenRequest(short, max_new_tokens=2))
        a.tokens(), b.tokens()
        rec = _log(eng)
        assert rec[b.id]["cached"] == 0 and rec[b.id]["seq_first_row"] < rec[a.id]["seq_last_row"]
    finally:
        eng.close()

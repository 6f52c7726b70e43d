"""Window and full attention layers in one stack over two kinds of paged
state, and one chip's share of the routed experts, on the CPU at the float32
twin of K-EXAONE-236B-A23B with seeded weights (benchmarks/configs/
k-exaone-236b-a23b.json, `rehearsal`: window 8, one dense layer and two
periods of three window layers and a full one, 16 experts top-4 of which 4
are held).

Compared on LOGITS against the benchmark's plain reference (float32,
benchmarks/hybrid_moe_reference.py), which shares no code with the program
and is given the same share. Tolerances, and why:

- `EXACT` 2e-4 on logits of order 1: float32 on both sides, the same int8
  weights, weight-only matmuls (`qmm`); what is left is the order of float32
  additions (paged against whole-sequence, a ring view against a band mask, a
  grouped matmul against a per-expert loop). Measured at most 2e-5. Any
  missing term (a rotation on a full layer, a q/k norm, a key outside the
  window or a lost one inside it, a dropped pair, the scale 2.5) moves logits
  by 1e-2 and more.
- prompt chunks are served W8A8 (`qmm_a8`), which at these tiny widths moves
  a logit by up to ~1: those programs are compared weight-only here (the
  fixture swaps `qmm_a8` for `qmm`), and as served by the benchmark's own gap
  check (`run.py --rehearse`, the last tests of this file).
- the int4 control has to FAIL the same comparison: measured 0.3 and more.
"""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.kvcache import CacheManager, mixed_kv_refusal, paged_default
from gofr_tpu.kvcache.paged import BlockPool, WindowTables
from gofr_tpu.llm import GenRequest, LLMEngine, mixed_refusal
from gofr_tpu.llm_programs import Programs
from gofr_tpu.models import TransformerConfig
from gofr_tpu.models import moe as M
from gofr_tpu.models import transformer as T
from gofr_tpu.models.quant import qmm
from gofr_tpu.profiling import mfu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
EXACT = 2e-4
SEED = 2**31 + 33


def _bench(name):
    """A module of benchmarks/ (the plain reference's side), by name."""
    sys.path[:0] = [BENCH]
    try:
        return __import__(name)
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def config_file():
    with open(os.path.join(BENCH, "configs", "k-exaone-236b-a23b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def twin(config_file):
    """(model group, family module, program config, program params) of the
    configuration's CPU twin."""
    model = config_file["rehearsal"]["model"]
    family = _bench("run").load_family(config_file)
    return model, family, family.program_config(model), family.program_params(model, SEED)


@pytest.fixture()
def weight_only(monkeypatch):
    """Prompt chunks with weight-only matmuls (see the module's docstring)."""
    monkeypatch.setattr(T, "qmm_a8", qmm)


def _tokens(n, s, vocab=512, seed=0):
    return np.random.default_rng(seed).integers(1, vocab, (n, s)).astype(np.int32)


# -- the configuration ---------------------------------------------------------------


def test_the_pattern_is_read_in_one_place(twin):
    _model, _family, cfg, params = twin
    assert cfg.windows == (8, 8, 8, 0, 8, 8, 8, 0, 8) and cfg.mixed and cfg.window == 8
    assert T.layer_kinds(cfg) == ((3, 7), (0, 1, 2, 4, 5, 6, 8))
    assert cfg.group_sizes == (1, 8) and (cfg.n_experts, cfg.held_experts, cfg.moe_top_k) == (16, 4, 4)
    dense, moe = params["layers"]
    assert dense["q_norm"].shape == (1, 16) and moe["k_norm"].shape == (8, 16)
    assert moe["w_router"].shape == (8, 64, 16) and moe["w_gate"].q.shape == (8, 4, 64, 32)
    # one scalar window stays the special case: nothing is mixed about mistral's stack
    plain = TransformerConfig.tiny_mistral()
    assert plain.windows == (8, 8) and not plain.mixed and T.layer_kinds(plain) == ((), (0, 1))


@pytest.mark.parametrize("kw,why", [
    (dict(layer_windows=(8, 0)), "names 2 layers"),
    (dict(layer_windows=(8, 4, 0, 0, 0, 0, 0, 0, 0)), "more than one window size"),
    (dict(layer_windows=(8,) * 9), "say it as sliding_window"),
    (dict(layer_windows=(8, 0, 0, 0, 0, 0, 0, 0, 0), sliding_window=8), "layer_windows alone"),
    (dict(moe_first_expert=14, moe_held_experts=4), "are not among the model's 16"),
])
def test_a_pattern_that_cannot_be_served_is_refused_when_the_config_is_built(kw, why):
    import dataclasses

    base = TransformerConfig.tiny_mixed_moe()
    with pytest.raises(ValueError, match=why):
        dataclasses.replace(base, **{"layer_windows": base.layer_windows, **kw})


# -- the whole-sequence forward pass ----------------------------------------------


def test_the_forward_pass_equals_the_reference(twin, weight_only):
    """q/k norm, rotation on window layers only, the band of 8 keys, the share
    of the experts: the program's own whole-sequence pass gives the reference's
    logits, at contexts five times the window."""
    model, family, cfg, params = twin
    toks = _tokens(2, 40)
    pos = jnp.broadcast_to(jnp.arange(40), (2, 40))
    got, _ = jax.jit(lambda p, t: T.transformer_forward(p, cfg, t, pos))(params, jnp.asarray(toks))
    want = np.asarray(family.forward_logits(model, SEED, toks))
    assert np.abs(np.asarray(got) - want).max() < EXACT
    assert want.std() > 0.5  # logits of order 1: the tolerance means something


def test_the_int4_control_fails_the_same_comparison(twin):
    model, family, _cfg, _params = twin
    toks = _tokens(2, 40)
    want = np.asarray(family.forward_logits(model, SEED, toks))
    low = np.asarray(family.forward_logits(model, SEED, toks, int4=True))
    assert np.abs(low - want).max() > 1000 * EXACT


@pytest.mark.parametrize("broken,why", [
    ("rope_on_window_only", "a rotation on the full layers"),
    ("qk_norm", "no q/k norm"),
    ("moe_scale", "another scale on the routed experts"),
])
def test_a_missing_piece_of_the_layer_fails_it_too(twin, weight_only, broken, why):
    """The comparison sees each thing the configuration's `assumed` states."""
    import dataclasses

    model, family, cfg, params = twin
    wrong = dataclasses.replace(cfg, **{broken: 1.0 if broken == "moe_scale" else False})
    toks = _tokens(1, 40)
    pos = jnp.arange(40)[None]
    got, _ = T.transformer_forward(params, wrong, jnp.asarray(toks), pos)
    want = np.asarray(family.forward_logits(model, SEED, toks))
    assert np.abs(np.asarray(got) - want).max() > 50 * EXACT, why


# -- prefill, then decode, through both pools ---------------------------------------


def _forced(forced):
    """A sampler that records each step's logits and feeds the GIVEN next token."""

    def sample(logits, temps, key, state):
        i, buf = state
        return forced[i], (i + 1, buf.at[i].set(logits))

    return sample


def _serve(cfg, params, seqs, prompt_lens, *, chunk=16, K=4, poison=True):
    """Serve `seqs` [b, total] as the engine does: a CacheManager for the two
    kinds' tables (blocks as the cursor advances, the window kind's given back
    behind the window), prompt chunks and decode chunks through the engine's
    own layout (llm_programs._Pool), every token forced. With `poison`, every
    FREE block of both pools is overwritten with 1e4 before each program: a
    freed block that a stale table entry still names may hold anything.
    Returns ({(lane, position): logits}, the manager)."""
    b, total = seqs.shape
    kv = CacheManager(cfg, b, 128, K, append_widths=(K, chunk), paged=paged_default())
    progs = Programs(cfg, kv, slots=b, decode_chunk=K, chunk_shapes=(chunk,), spec_draft=0, mesh=None,
                     tp_gather=None, kernel=False, numeric_check=False, label="test", metrics=None)
    lay = progs.layout
    cache, _ = kv.pool_arrays(jnp)
    state = {"params": params, "scales": jnp.zeros((0,), jnp.float32), "moe_out": []}
    for i in range(b):
        assert kv.admit_reserve(int(prompt_lens[i]), total - int(prompt_lens[i]), None)
        kv.attach_seed(i, None, object(), int(prompt_lens[i]), total - int(prompt_lens[i]))

    def tables():
        return jnp.asarray(kv._tables_np if kv.take_tables() is None else kv._tables_np)

    def poisoned(cache):
        if not poison:
            return cache
        free = [jnp.asarray(p._free or [p.n_blocks], jnp.int32) for p in (kv.pool, kv.window_tables.pool)]
        return cache._replace(
            k=tuple(k.at[:, f].set(1e4, mode="drop") for k, f in zip(cache.k, free)),
            v=tuple(v.at[:, f].set(1e4, mode="drop") for v, f in zip(cache.v, free)))

    append = jax.jit(lambda cache, tbl, toks, cur, nn: _append(lay, state, cache, tbl, toks, cur, nn))
    out, cursors = {}, np.zeros((b,), np.int64)
    while (cursors < prompt_lens).any():
        n_new = np.minimum(chunk, prompt_lens - cursors)
        toks = np.zeros((b, chunk), np.int32)
        for i in range(b):
            toks[i, : n_new[i]] = seqs[i, cursors[i] : cursors[i] + n_new[i]]
            kv.ensure(i, int(cursors[i] + n_new[i]))
        logits, cache = append(poisoned(cache), tables(), jnp.asarray(toks), jnp.asarray(cursors, jnp.int32),
                               jnp.asarray(n_new, jnp.int32))
        for i in range(b):
            if n_new[i] > 0:
                out[i, int(cursors[i] + n_new[i] - 1)] = np.asarray(logits[i])
        cursors = cursors + n_new
    lengths = np.array(prompt_lens)
    tail = jnp.asarray([seqs[i, lengths[i]] for i in range(b)], jnp.int32)

    def chunk_fn(cache, tbl, tail, forced):
        a = {**state, "cache": cache, "tables": tbl, "tail": tail, "active": jnp.ones((b,), bool),
             "live": jnp.ones((b,), bool), "temps": jnp.zeros((b,)), "rng": jax.random.PRNGKey(0), "moe_out": []}
        st0 = (jnp.int32(0), jnp.zeros((K, b, cfg.vocab_size), jnp.float32))
        _toks, (st,) = lay.decode(a, K, _forced(forced), {"sample_state": st0})
        return a["cache"], a["tail"], st[1]

    decode = jax.jit(chunk_fn)
    while (lengths + K < total).all():
        forced = jnp.asarray(np.stack([seqs[np.arange(b), lengths + j + 1] for j in range(K)]), jnp.int32)
        for i in range(b):
            kv.ensure(i, int(lengths[i] + K))
        cache, tail, buf = decode(poisoned(cache), tables(), tail, forced)
        for j in range(K):
            for i in range(b):
                out[i, int(lengths[i] + j)] = np.asarray(buf[j, i])
        lengths = lengths + K
    return out, kv


def _append(lay, state, cache, tbl, toks, cur, nn):
    a = {**state, "cache": cache, "tables": tbl, "moe_out": []}
    logits = lay.append(a, toks, jnp.arange(toks.shape[0], dtype=jnp.int32), cur, nn, None)
    return logits, a["cache"]


def test_prefill_then_decode_through_both_pools_equals_the_reference(twin, weight_only):
    """Two lanes: prompts of 21 and 53 tokens in chunks of 16 (lane 1's last
    chunks start far past the window of 8 and behind reclaimed blocks), then
    forced decode in chunks of 4 to 116 tokens. Every logit either program gives
    is the reference's full forward pass's, the window layers never held more
    than their bound, blocks behind the window went back while the context grew,
    and what a freed block holds (1e4 everywhere) reached no logit."""
    model, family, cfg, params = twin
    seqs = _tokens(2, 120, seed=3)
    got, kv = _serve(cfg, params, seqs, np.array([21, 53]))
    want = np.asarray(family.forward_logits(model, SEED, seqs))
    assert {(0, 20), (1, 52), (0, 21), (1, 53), (0, 80), (1, 112)} <= set(got)
    worst = max(np.abs(lg - want[i, p]).max() for (i, p), lg in got.items())
    assert worst < EXACT, worst
    w = kv.stats()["kinds"]["window"]
    assert w["blocks_reclaimed"] >= 6 and w["peak_blocks_per_slot"] <= w["bound_blocks_per_slot"]
    assert w["blocks_in_use"] < w["blocks_unreclaimed"] == kv.stats()["kinds"]["full"]["blocks_in_use"]


def test_a_window_layer_that_lost_a_key_inside_its_window_fails(twin, weight_only, monkeypatch):
    """The control of the test above: give blocks back one block too early (a
    window thought to be one key, a margin of minus a block: the block that
    holds the keys just below the cursor goes too) and what the freed block
    was overwritten with is read."""
    model, family, cfg, params = twin
    real = WindowTables.advance

    def eager(self, slot, upto):
        self.margin, self.window = -self.block, 1
        return real(self, slot, upto)

    monkeypatch.setattr(WindowTables, "advance", eager)
    seqs = _tokens(1, 80, seed=4)
    got, _kv = _serve(cfg, params, seqs, np.array([40]))
    want = np.asarray(family.forward_logits(model, SEED, seqs))
    worst = max(np.abs(lg - want[i, p]).max() for (i, p), lg in got.items())
    assert worst > 100 * EXACT


# -- the window kind's bound, and no block leaked ----------------------------------------


def test_window_blocks_stay_bounded_while_a_context_grows_to_max_seq_len():
    """The host's bookkeeping alone, at K-EXAONE's sizes: 16-token blocks, a
    window of 128, steps of a 64-token prompt chunk (+ the decode chunk where
    the prompt ends) and then decode chunks of 8 up to 8,192 tokens."""
    cfg = TransformerConfig.tiny_mixed_moe()
    cfg = TransformerConfig(**{**cfg.__dict__, "layer_windows": tuple(128 if w else 0 for w in cfg.layer_windows)})
    kv = CacheManager(cfg, 2, 8192, 8, append_widths=(8, 16, 64), paged=paged_default())
    wt = kv.window_tables
    # the issue's arithmetic: 128 - 1 keys below a step's first query, 16 of merge slack, a step's 64 + 8 rows
    assert wt.bound == 15 and wt.pool.n_blocks == 2 * 15 and kv.table_cols == 2 * kv.table_width
    assert kv.admit_reserve(6000, 2192, None)
    kv.attach_seed(0, None, "r", 6000, 2192)
    pos, most = 0, 0
    while pos < 6000:
        n = min(64, 6000 - pos)
        pos += n
        kv.ensure(0, pos + (8 if pos == 6000 else 0))
        most = max(most, wt.held(0))
    pos += 8
    while pos < 8192:
        pos += 8
        kv.ensure(0, pos)
        most = max(most, wt.held(0))
        # every key of the window of the NEXT program's first query is still held
        assert wt.lo[0] * 16 <= max(0, pos - 127)
    assert most <= wt.bound and wt.peak == most
    full = kv.stats()["kinds"]["full"]["blocks_in_use"]
    assert full == -(-8192 // 16) and wt.stats()["blocks_unreclaimed"] == full
    assert wt.reclaimed == full - wt.held(0)
    kv.release_slot(0, "r")
    st = kv.stats()
    assert st["blocks_in_use"] == 0 and st["kinds"]["window"]["blocks_in_use"] == 0
    assert st["blocks_reserved"] == 0 and len(wt.pool._free) == wt.pool.n_blocks


def test_the_engine_serves_it_and_every_block_comes_back(twin):
    """Through LLMEngine as the benchmark drives it (W8A8 prompt chunks, the
    fused step): six requests over four slots, contexts to ten windows; greedy
    tokens do not depend on who shares the batch; the records count the share;
    every block of both kinds is back once the requests have ended."""
    _model, _family, cfg, params = twin
    kw = dict(slots=4, max_seq_len=128, prefill_buckets=(16, 64), decode_chunk=8, warmup=False, quantize=True)
    prompts = [list(range(1 + i, 30 + 9 * i)) for i in range(6)]
    alone = LLMEngine(cfg, params, **kw)
    try:
        want = alone.generate(prompts[5], max_new_tokens=40)
    finally:
        alone.close()
    eng = LLMEngine(cfg, params, **kw)
    try:
        reqs = [eng.submit(GenRequest(p, max_new_tokens=40, temperature=0.0, eos_token=-1)) for p in prompts]
        outs = [list(r.stream()) for r in reqs]
        assert all(len(o) == 40 for o in outs) and outs[5] == want
        deadline = time.time() + 20
        while eng.stats()["kvcache"]["blocks_in_use"] and time.time() < deadline:
            time.sleep(0.05)
        st = eng.stats()
        kinds = st["kvcache"]["kinds"]
        assert st["kvcache"]["blocks_in_use"] == 0 == kinds["window"]["blocks_in_use"]
        assert kinds["window"]["blocks_reclaimed"] > 0
        assert kinds["window"]["peak_blocks_per_slot"] <= kinds["window"]["bound_blocks_per_slot"]
        assert (kinds["full"]["layers"], kinds["window"]["layers"]) == (2, 7)
        assert st["attention"]["decode"].startswith("xla_gather") and "window: xla_gather band 8" in st["attention"]["decode"]
        assert all("window: xla ring of 72" in p for p in st["attention"]["prefill"].values())
        moe = st["moe"]
        assert moe["held"] == {"first": 0, "count": 4, "of": 16} and len(moe["tokens_per_expert"]) == 4
        assert moe["pairs"] == sum(moe["tokens_per_expert"]) < moe["pairs_routed"]
        fields = st["step_log"]["fields"]
        n_moe, k = cfg.n_layers - cfg.n_dense_layers, cfg.moe_top_k
        for rec in map(lambda r: dict(zip(fields, r)), st["step_log"]["records"]):
            tokens = rec["k"] * 4 + sum(shape for _start, _n, shape in rec["rows"])
            assert rec["moe_pairs_routed"] == tokens * k * n_moe and rec["moe_pairs"] <= rec["moe_pairs_routed"]
            assert rec["moe_touched"] <= 4 * n_moe * (rec["k"] + (rec["kind"] == "step"))
            # a lane's context in a record is its own: a full layer reads all of it
            assert all(isinstance(c, int) for c, _n in rec["decode_ctx"])
        assert max(c for rec in st["step_log"]["records"] for c, _n in rec[fields.index("decode_ctx")]) > 8 * 8
    finally:
        eng.close()


# -- what is refused, with a sentence -----------------------------------------------------


def test_what_was_written_for_one_pool_is_refused_for_a_mixed_stack(twin):
    _model, _family, cfg, params = twin
    kw = dict(slots=2, max_seq_len=128, warmup=False, quantize=True)
    for extra, why in (
        (dict(prefix_cache_mb=1), "prefix sharing and sessions"),
        (dict(session_mb=1), "prefix sharing and sessions"),
        (dict(kv_int8=True), "int8 KV pool"),
        (dict(kv_paged=False), "paged pools only"),
        (dict(step_token_budget=0), "token-budget step scheduler"),
        (dict(speculative=True), "speculative decoding"),
        (dict(lora_slots=2), "LoRA"),
    ):
        with pytest.raises(ValueError, match=why):
            LLMEngine(cfg, params, **kw, **extra)
    assert mixed_refusal(mesh=None, chunked=True, speculative=False, lora_slots=0) is None
    assert mixed_kv_refusal(int8=False, retain_bytes=0, session_bytes=0) is None
    with pytest.raises(ValueError, match="whole-sequence form only"):
        T.decode_step(params, cfg, jnp.zeros((1,), jnp.int32), T.init_cache(cfg, 1, 16))


# -- the shares of an expert layer add up -----------------------------------------------


def test_the_shares_add_up_to_the_uncut_layer(config_file):
    """The first routed layer of the twin, over 48 token rows: the routed parts
    that the four shares of 4 experts compute (the program's routed_ffn, each
    told which experts it holds, over the family's weights for that share),
    plus the shared expert once, are the uncut REFERENCE layer's result (all 16
    experts held), and every pair the router chose was computed by exactly one."""
    run = _bench("run")
    family = run.load_family(config_file)
    W, REF = _bench("hybrid_moe_weights"), _bench("hybrid_moe_reference")
    shared_model = config_file["rehearsal"]["model"]
    uncut = {**shared_model, "num_experts": 16, "first_expert_held": 0}
    h = jax.random.normal(jax.random.PRNGKey(5), (48, 64), jnp.float32)
    lkey = W.layer_keys(W.base_key(SEED), uncut)[1]
    leaves = W.layer_leaves({k: v for k, v in uncut.items() if not isinstance(v, (list, dict))}, lkey, True)
    with jax.default_matmul_precision("highest"):
        want = REF.routed_part({**uncut, "rope_theta": 1e6}, leaves, h) + REF.shared_part(uncut, leaves, h)
    got, pairs = 0.0, 0
    for first in (0, 4, 8, 12):
        model = {**shared_model, "num_experts": 4, "first_expert_held": first}
        cfg = family.program_config(model)
        lp = jax.tree.map(lambda a: a[0], family.program_params(model, SEED)["layers"][1])
        if first:  # what every chip computes alike is counted once
            lp = {k: v for k, v in lp.items() if not k.startswith("ws_")}
        y, counts = M.routed_ffn(cfg, h, lp, qmm)
        got, pairs = got + y, pairs + int(counts.sum())
        assert counts.shape == (4,)
    assert pairs == 48 * 4
    assert float(jnp.abs(got - want).max()) < EXACT and float(jnp.abs(want).mean()) > 0.05


def test_every_expert_held_is_the_function_it_was(twin):
    """`moe_held_experts` = all of them traces the very program that no share
    traces (glm's engines' pinned programs rest on it: test_engine_programs)."""
    import dataclasses

    _model, _family, cfg, _params = twin
    all_held = dataclasses.replace(cfg, moe_first_expert=0, moe_held_experts=16)
    none_said = dataclasses.replace(cfg, moe_first_expert=0, moe_held_experts=0)
    h = jnp.zeros((8, 64), jnp.float32)
    lp = jax.tree.map(lambda a: a[1], T.init_params(jax.random.PRNGKey(0), none_said)["layers"][1])
    texts = [jax.jit(lambda h, lp, c=c: M.routed_ffn(c, h, lp, qmm)).lower(h, lp).as_text() for c in (all_held, none_said)]
    assert texts[0] == texts[1]


# -- counts, against numbers worked out by hand ---------------------------------------------


def test_the_cut_is_the_issues_table(config_file):
    """K-EXAONE-236B-A23B at the cell's sizes, nothing allocated: the weights a
    chip of eight holds, what the two pools hold, a token's own work."""
    C = _bench("hybrid_moe_costs")
    model = config_file["model"]
    assert C.attention_matmul_params(model) == 6144 * 8192 + 6144 * 2048 + 8192 * 6144 == 113_246_208
    assert C.expert_params(model) == 3 * 6144 * 2048 == 37_748_736
    assert C.pairs_here_per_token(model) == 1.0  # 8 x 16 / 128
    assert C.layer_matmul_params(model, False) == 113_246_208 + 3 * 6144 * 18432 == 452_984_832
    assert C.layer_matmul_params(model, True) == 113_246_208 + 2 * 37_748_736 + 6144 * 128
    assert abs(C.weight_bytes(model) / 1e9 - 9.79) < 0.03  # 453 + 12 x 758 + 236 MB
    assert C.keys_read(model, 5000) == 3 * 5000 + 10 * 128 and C.keys_read(model, 100) == 13 * 100
    assert C.decode_kv_read_bytes(model, [5000, 100]) == 4096 * (3 * 5000 + 10 * 128 + 13 * 100)
    assert C.attention_flops(model, 1000) == 4 * 64 * 128 * (3 * 1000 + 10 * 128)
    pk = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9}
    # 16 pairs over 10 touched experts: the stream binds (10 x 37.75 MB / 819 GB/s = 461 us)
    assert abs(C.moe_least_seconds(model, pk, pairs=16, touched=10) - 10 * 37_748_736 / 819e9) < 1e-12
    family = _bench("run").load_family(config_file)
    cfg = family.program_config(model)
    assert cfg.windows.count(0) == 3 and cfg.windows.count(128) == 10 and cfg.held_experts == 16
    kv = CacheManager(cfg, 16, 8192, 8, append_widths=(8, 16, 64), paged=paged_default())
    st = kv.stats()
    full, window = st["kinds"]["full"], st["kinds"]["window"]
    assert full["block_bytes"] == 3 * 16 * 4096 and window["block_bytes"] == 10 * 16 * 4096
    assert abs(full["pool_blocks"] * full["block_bytes"] / 1e9 - 1.61) < 0.01  # 16 x 8,208 x 3 x 4,096 B
    assert window["pool_blocks"] == 16 * 15 and abs(window["pool_blocks"] * window["block_bytes"] / 1e9 - 0.157) < 0.001
    one_pool = 16 * 8208 * 13 * 4096
    assert abs(one_pool / 1e9 - 6.99) < 0.01  # what one pool for all 13 layers would take: it does not fit
    assert (st["pool_blocks"], st["block_bytes"]) == (full["pool_blocks"], full["block_bytes"])


# -- the accounting a scalar window made wrong ----------------------------------------------


def test_mfu_counts_attention_by_each_layers_window(twin):
    """7 window layers (8 keys) and 2 full ones: a token at context 100 attends
    (7 x 8 + 2 x 100) / 9 positions a layer on average, not 100 (cfg.sliding_window
    is 0) and not 8."""
    _model, _family, cfg, _params = twin
    costs = mfu.model_costs(cfg, quantized=True)
    assert costs.sliding_window == 0 and costs.layer_windows == cfg.windows
    assert mfu.read_ctx(costs, 100) == pytest.approx((7 * 8 + 2 * 100) / 9)
    assert mfu.read_ctx(costs, 5) == 5
    tri = lambda p, w: p * (p + 1) / 2 if not w or p <= w else w * (w + 1) / 2 + (p - w) * w  # noqa: E731
    assert mfu.attended_below(costs, 50) == pytest.approx((7 * tri(50, 8) + 2 * tri(50, 0)) / 9)
    per_ctx = costs.attn_flops_per_token_per_ctx
    assert mfu.decode_flops(costs, 1, mfu.read_ctx(costs, 100)) - costs.matmul_flops_per_token == pytest.approx(
        per_ctx * (7 * 8 + 2 * 100) / 9)
    got = mfu.chunk_prefill_flops(costs, [(32, 16)])
    attended = (7 * (tri(48, 8) - tri(32, 8)) + 2 * (tri(48, 0) - tri(32, 0))) / 9
    assert got == pytest.approx(2 * 16 * costs.layer_params + 2 * costs.embed_params + per_ctx * attended)
    # the share: a token multiplies 4 x 4 / 16 of its routed experts here, 4 + 1 are resident
    d, fe = cfg.d_model, cfg.moe_d_ff
    attn = d * (4 + 2 * 2) * 16 + 4 * 16 * d
    assert costs.layer_params == 9 * attn + 3 * d * cfg.d_ff + 8 * (3 * d * fe * (1 + 1) + d * 16)
    # one window for the whole stack reads as it did
    plain = mfu.model_costs(TransformerConfig.tiny_mistral())
    assert plain.layer_windows == () and mfu.read_ctx(plain, 100) == 8 and mfu.attended_below(plain, 50) == tri(50, 8)


def test_the_engines_records_keep_a_mixed_stacks_whole_context(twin):
    _model, _family, cfg, params = twin
    eng = LLMEngine(cfg, params, slots=2, max_seq_len=128, warmup=False, quantize=True)
    try:
        r = GenRequest(list(range(1, 41)), max_new_tokens=4)
        r.emitted = 10
        assert eng._ctx_of(r) == 50 and eng._ctx_read(r) == pytest.approx((7 * 8 + 2 * 50) / 9)
    finally:
        eng.close()


# -- the cache's defaults ------------------------------------------------------------------------


def test_auto_pages_a_mixed_stack_and_keeps_the_ring_for_one_window(twin):
    """`paged_default` ("auto"): a mixed stack goes to the pools (its window
    layers bounded a slot by reclaim, which the ring could not give its full
    layers); a stack with ONE window for every layer keeps the rolling ring."""
    _model, _family, cfg, _params = twin
    kv = CacheManager(cfg, 2, 128, 8, append_widths=(8, 16), paged=paged_default())
    assert kv.paged and kv.mixed and kv.window_tables.bound == -(-(8 - 1 + 16 + 16 + 8) // 16) + 1
    assert [len(s) for s in kv.pool_shapes()] == [2, 2] and kv.pool_shapes()[0][1][0] == 7
    ring = CacheManager(TransformerConfig.tiny_mistral(), 2, 128, 8, append_widths=(8, 16), paged=paged_default())
    assert not ring.paged and ring.rolling and not ring.mixed
    assert "gives a block" in BlockPool.__doc__ and "MIXED" in paged_default.__doc__

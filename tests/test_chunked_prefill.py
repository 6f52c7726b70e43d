"""Chunked prefill + token-budget step scheduler (gofr_tpu.llm).

The load-bearing invariant: the chunked scheduler is a SCHEDULING change,
never a model change — an engine that appends prompts chunk by chunk
under a token budget must emit exactly the tokens the monolithic-wave
engine (step_token_budget=0) and the standalone generate() emit, across
dense KV, rolling-window KV, prefix-cache seeding (exact AND mid-prompt),
and prompt lengths straddling every chunk boundary.

Device-level pieces get their own checks: prefill_append vs prefill on
raw caches, chunk_prefill_attention's masks, and the flash kernel's
q_offsets path (interpret mode). Exhaustive boundary sweeps are marked
slow (tier-1 runs -m 'not slow'; CI's full run keeps them).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.llm import GenRequest, LLMEngine
from gofr_tpu.models import TransformerConfig, generate, init_params
from gofr_tpu.models.transformer import init_cache, prefill, prefill_append
from gofr_tpu.ops import chunk_prefill_attention, mha_reference

CFG = TransformerConfig.tiny()
CFGW = TransformerConfig.tiny_mistral()  # sliding window 8


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def params_w():
    return init_params(jax.random.PRNGKey(3), CFGW)


_REF_PAD = 32  # fixed reference shapes: one generate/prefill compile per
# max_new_tokens value instead of one per prompt length (tier-1 runtime)


def _reference(params, cfg, prompt: list[int], n: int) -> list[int]:
    toks = np.zeros((1, _REF_PAD), np.int32)
    toks[0, : len(prompt)] = prompt
    lens = jnp.asarray([len(prompt)], jnp.int32)
    return [
        int(t)
        for t in np.asarray(generate(params, cfg, jnp.asarray(toks), lens, n))[0]
    ]


def _ref_prefill_logits(params, cfg, prompt: list[int]):
    """Monolithic-prefill last-token logits at a fixed padded shape."""
    toks = np.zeros((1, _REF_PAD), np.int32)
    toks[0, : len(prompt)] = prompt
    logits, _ = prefill(
        params, cfg, jnp.asarray(toks),
        jnp.asarray([len(prompt)], jnp.int32), _REF_PAD,
    )
    return logits


class TestPrefillAppendOp:
    """Device-level equality: chunked appends reproduce monolithic
    prefill's last-token logits argmax on the same cache rows."""

    @pytest.mark.parametrize("plen,chunks", [
        (3, [8]), (9, [8, 8]), (16, [8, 8]), (17, [8, 8, 8]), (30, [16, 16]),
    ])
    def test_dense_matches_monolithic(self, params, plen, chunks):
        rng = np.random.default_rng(plen)
        prompt = rng.integers(1, CFG.vocab_size, plen).tolist()
        logits_ref = _ref_prefill_logits(params, CFG, prompt)
        cache = init_cache(CFG, 1, 64)
        pos = 0
        for c in chunks:
            n = min(c, plen - pos)
            if n <= 0:
                break
            block = np.zeros((1, c), np.int32)
            block[0, :n] = prompt[pos : pos + n]
            logits, cache = prefill_append(
                params, CFG, jnp.asarray(block), cache,
                jnp.asarray([pos], jnp.int32), jnp.asarray([n], jnp.int32),
            )
            pos += n
        assert pos == plen
        assert int(jnp.argmax(logits[0])) == int(jnp.argmax(logits_ref[0]))
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(logits_ref), atol=1e-4
        )

    def test_ring_append_wraps_and_matches(self, params_w):
        """Rolling ring: appends wrap mod capacity; logits match the
        ring-packed monolithic prefill even when the prompt exceeds the
        ring (oldest rows are overwritten, all in-window rows survive)."""
        C = 8 + 16  # window + chunk slack
        for plen in (5, 20, 30):
            rng = np.random.default_rng(plen)
            prompt = rng.integers(1, CFGW.vocab_size, plen).tolist()
            logits_ref = _ref_prefill_logits(params_w, CFGW, prompt)
            cache = init_cache(CFGW, 1, C)
            pos = 0
            while pos < plen:
                n = min(16, plen - pos)
                block = np.zeros((1, 16), np.int32)
                block[0, :n] = prompt[pos : pos + n]
                logits, cache = prefill_append(
                    params_w, CFGW, jnp.asarray(block), cache,
                    jnp.asarray([pos], jnp.int32),
                    jnp.asarray([n], jnp.int32), ring=C,
                )
                pos += n
            assert int(jnp.argmax(logits[0])) == int(jnp.argmax(logits_ref[0]))


def _gathered_form(params, cfg, tokens, cache, slots, cursors, n_new, ring):
    """The contiguous layouts' append as every step ran it before PR 34: take
    the rows' whole slots out of the stack, append to that copy, write the
    whole slots back (padding lanes, slot = S, clip to a real slot and drop
    their write). What the `slots` form of prefill_append must equal, bit
    for bit."""
    sub = cache._replace(
        k=jnp.take(cache.k, slots, axis=1, mode="clip"),
        v=jnp.take(cache.v, slots, axis=1, mode="clip"), length=cursors,
    )
    logits, sub = prefill_append(params, cfg, tokens, sub, cursors, n_new, ring=ring)
    return logits, cache._replace(
        k=cache.k.at[:, slots].set(sub.k, mode="drop"),
        v=cache.v.at[:, slots].set(sub.v, mode="drop"),
        length=cache.length.at[slots].set(cursors + n_new, mode="drop"),
    )


# (slots, cursors, n_new) a step, over a stack of 4 slots and rows of 16:
# slot 0 takes 51 tokens in all, twice round a ring of 24
_STEPS = [
    ([2, 0], [0, 0], [16, 16]),  # a packed subset, in another order than the slots'
    ([0, 2, 4], [16, 16, 0], [16, 5, 0]),  # n_new < c beside a full row, and a padding lane (slot 4 of 4)
    ([3, 4, 0, 4], [0, 0, 32, 0], [16, 0, 16, 0]),  # padding lanes between real ones; slot 0 rolls the ring
    ([0], [48], [3]),
]


class TestAppendInPlace:
    """prefill_append's `slots` form (llm_programs._Slab: the engine's whole
    stack and the packed rows' slots, the chunk's rows written where the
    stack lies) against the gather / append / write-back it replaced."""

    @pytest.mark.parametrize("layout", ["ring", "slab"])
    def test_equals_the_gathered_form_step_by_step(self, layout, params, params_w):
        cfg, prm, cap, ring = (
            (CFGW, params_w, 24, 24) if layout == "ring" else (CFG, params, 64, 0)
        )
        rng = np.random.default_rng(34)
        cache = init_cache(cfg, 4, cap)
        # rows of a previous occupant everywhere: what an append must not disturb
        cache = cache._replace(
            k=jnp.asarray(rng.standard_normal(cache.k.shape), cache.k.dtype),
            v=jnp.asarray(rng.standard_normal(cache.v.shape), cache.v.dtype),
        )
        theirs = mine = cache
        prompt0: list[int] = []
        for slots, cursors, n_new in _STEPS:
            toks = rng.integers(1, cfg.vocab_size, (len(slots), 16)).astype(np.int32)
            if 0 in slots:
                i = slots.index(0)
                prompt0 += toks[i, : n_new[i]].tolist()
            args = [jnp.asarray(x, jnp.int32) for x in (slots, cursors, n_new)]
            want, theirs = _gathered_form(prm, cfg, jnp.asarray(toks), theirs, *args, ring)
            got, mine = prefill_append(
                prm, cfg, jnp.asarray(toks), mine, args[1], args[2], ring=ring, slots=args[0]
            )
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
            for a, b in zip(mine, theirs):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert mine.length.tolist() == [51, 0, 21, 16]
        # ...and slot 0's last logits are the monolithic prefill's of its 51 tokens
        toks = np.zeros((1, 64), np.int32)
        toks[0, :51] = prompt0
        ref, _ = prefill(prm, cfg, jnp.asarray(toks), jnp.asarray([51], jnp.int32), 64)
        assert int(jnp.argmax(got[0])) == int(jnp.argmax(ref[0]))
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref[0]), atol=2e-4)

    def test_the_one_scatter_is_the_merges_it_replaced(self):
        """_land_rows against the two writes of the parent: the ring merge
        vmapped over the slot axis (decode_chunk) and a row's masked write
        into its own slot (the append), with lengths that wrap, a padding
        lane and positions past n_new."""
        from gofr_tpu.models.transformer import _land_rows

        rng = np.random.default_rng(7)
        L, S, C, K = 3, 4, 24, 8
        stack = jnp.asarray(rng.standard_normal((L, S, C, 2, 16)), jnp.float32)
        rows = jnp.asarray(rng.standard_normal((L, S, K, 2, 16)), jnp.float32)
        idx = jnp.mod(jnp.asarray([0, 20, 47, 23])[:, None] + jnp.arange(K), C)
        merge = jax.vmap(lambda c, u, ix: c.at[:, ix].set(u), in_axes=(1, 1, 0), out_axes=1)
        np.testing.assert_array_equal(
            np.asarray(_land_rows(stack, rows, jnp.arange(S), idx)), np.asarray(merge(stack, rows, idx))
        )
        # the append: rows of slots 2, (padding), 0; indices past n_new are C and dropped
        slots = jnp.asarray([2, S, 0])
        idx3 = jnp.asarray([[22, 23, 0, 1, C, C, C, C], [C] * K, list(range(8, 16))])
        want = stack
        for i, s_ in enumerate([2, None, 0]):
            if s_ is not None:
                want = want.at[:, s_, idx3[i]].set(rows[:, i], mode="drop")
        np.testing.assert_array_equal(
            np.asarray(_land_rows(stack, rows[:, :3], slots, idx3)), np.asarray(want)
        )


class TestChunkPrefillAttention:
    def test_matches_reference_with_offsets(self):
        rng = np.random.default_rng(0)
        b, cap, c, hq, hkv, d = 2, 32, 8, 4, 2, 16
        k = jnp.asarray(rng.standard_normal((b, cap, hkv, d)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((b, cap, hkv, d)), jnp.float32)
        q = jnp.asarray(rng.standard_normal((b, c, hq, d)), jnp.float32)
        cursors = jnp.asarray([0, 11], jnp.int32)
        got = chunk_prefill_attention(q, k, v, cursors)
        want = mha_reference(
            q, k, v, causal=True,
            q_positions=cursors[:, None] + jnp.arange(c)[None, :],
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=1e-5
        )

    def test_ring_requires_window(self):
        q = jnp.zeros((1, 4, 2, 4))
        kc = jnp.zeros((1, 8, 1, 4))
        with pytest.raises(ValueError, match="ring"):
            chunk_prefill_attention(
                q, kc, kc, jnp.asarray([0]), window=0, ring=8
            )

    def test_flash_q_offsets_interpret_matches_reference(self):
        """The Pallas flash path accepts a query block attending to
        `prefill_pos` prior keys (per-batch offsets), verified in
        interpret mode against the masked reference."""
        from gofr_tpu.ops.attention import flash_attention

        rng = np.random.default_rng(1)
        b, cap, c, hq, hkv, d = 2, 256, 128, 4, 2, 128
        k = jnp.asarray(rng.standard_normal((b, cap, hkv, d)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((b, cap, hkv, d)), jnp.float32)
        q = jnp.asarray(rng.standard_normal((b, c, hq, d)), jnp.float32)
        offs = jnp.asarray([0, 97], jnp.int32)
        for window in (0, 64):
            got = flash_attention(
                q, k, v, causal=True, window=window, q_offsets=offs,
                interpret=True,
            )
            want = mha_reference(
                q, k, v, causal=True, window=window,
                q_positions=offs[:, None] + jnp.arange(c)[None, :],
            )
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), atol=1e-4
            )


def _engines(cfg, params, **kw):
    """(chunked, monolithic) engine pair — the A/B lever."""
    chunked = LLMEngine(cfg, params, warmup=False, **kw)
    kw = dict(kw, step_token_budget=0)
    mono = LLMEngine(cfg, params, warmup=False, **kw)
    assert chunked.stats()["scheduler"] == "chunked"
    assert mono.stats()["scheduler"] == "wave"
    return chunked, mono


class TestEngineEquality:
    """End-to-end: chunked scheduler tokens == monolithic tokens ==
    standalone generate()."""

    @pytest.fixture(scope="class")
    def dense(self, params):
        pair = _engines(
            CFG, params, slots=4, max_seq_len=64, prefill_buckets=(8, 16),
            step_token_budget=24, prefill_chunk=8,
        )
        yield pair
        for e in pair:
            e.close()

    @pytest.fixture(scope="class")
    def rolling(self, params_w):
        pair = _engines(
            CFGW, params_w, slots=2, max_seq_len=64, prefill_buckets=(16,),
            step_token_budget=32, prefill_chunk=16,
        )
        yield pair
        for e in pair:
            e.close()

    # 7 and 15 (just-below-boundary) ride in the slow dense_sweep
    @pytest.mark.parametrize("plen", [1, 8, 9, 16, 17])
    def test_dense_straddles_chunk_boundaries(self, dense, params, plen):
        chunked, mono = dense
        rng = np.random.default_rng(plen)
        prompt = rng.integers(1, CFG.vocab_size, plen).tolist()
        want = _reference(params, CFG, prompt, 8)
        assert mono.generate(prompt, max_new_tokens=8) == want
        assert chunked.generate(prompt, max_new_tokens=8) == want
        assert chunked.stats()["steps"] >= 1

    # 15/16 (boundary pair) ride in the slow rolling_sweep
    @pytest.mark.parametrize("plen", [4, 17, 30])
    def test_rolling_window_matches(self, rolling, params_w, plen):
        chunked, mono = rolling
        rng = np.random.default_rng(plen)
        prompt = rng.integers(1, CFGW.vocab_size, plen).tolist()
        want = _reference(params_w, CFGW, prompt, 10)
        assert mono.generate(prompt, max_new_tokens=10) == want
        assert chunked.generate(prompt, max_new_tokens=10) == want

    def test_concurrent_mixed_lengths_all_exact(self, dense, params):
        """Interleaved prefill chunks of several requests (coalesced into
        shared steps) must not contaminate each other."""
        import threading

        chunked, _ = dense
        rng = np.random.default_rng(42)
        prompts = [rng.integers(1, CFG.vocab_size, n).tolist()
                   for n in (3, 17, 9, 25, 1, 12)]
        expects = [_reference(params, CFG, p, 5) for p in prompts]
        results: list = [None] * len(prompts)

        def run(i):
            results[i] = chunked.generate(prompts[i], max_new_tokens=5)

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert results == expects

    def test_budget_bounds_prefill_tokens_per_step(self, params):
        """Every dispatched step packs at most max(budget, one chunk)
        prefill tokens — the head-of-line bound the scheduler exists
        for. Telemetry: step count, packed tokens, budget gauge."""
        from gofr_tpu.metrics import new_metrics_manager

        metrics = new_metrics_manager()
        eng = LLMEngine(
            CFG, params, slots=4, max_seq_len=64, prefill_buckets=(8,),
            step_token_budget=16, prefill_chunk=8, warmup=False,
            metrics=metrics,
        )
        try:
            reqs = [
                eng.submit(GenRequest(
                    np.random.default_rng(i).integers(
                        1, CFG.vocab_size, 20).tolist(),
                    max_new_tokens=4,
                ))
                for i in range(4)
            ]
            for r in reqs:
                assert len(r.tokens(timeout=60)) == 4
            s = eng.stats()
            # 4 prompts x 20 tokens at <=16 prefill tokens per step needs
            # at least ceil(80/16) = 5 steps
            assert s["steps"] >= 5
            assert s["step_tokens"] >= 80
            expo = metrics.render_prometheus()
            assert "app_llm_step_tokens" in expo
            assert "app_llm_step_seconds" in expo
            assert "app_llm_step_budget_utilization" in expo
        finally:
            eng.close()


class TestStepDeactivatesReusedSlot:
    """A freed slot keeps its device active=True (nothing clears it at
    finish; the wave path relied on admission rewriting the slot
    wholesale). The step op must clear it for mid-prefill rows —
    otherwise the decode merge keeps advancing the slot's length during
    a multi-chunk prefill and, on a rolling ring, the stale advance can
    wrap past the capacity slack and overwrite in-window rows."""

    def test_step_op_clears_active_for_mid_prefill_rows(self, params):
        eng = LLMEngine(
            CFG, params, slots=2, max_seq_len=64, prefill_buckets=(8,),
            prefill_chunk=8, step_token_budget=16, warmup=False,
            kv_paged=False,  # pins the dense step-op signature
        )
        try:
            op = eng._step_ops[8]
            pack = np.zeros((2, 8 + 3), np.int32)
            meta = np.zeros((2, 2), np.int32)
            # row 0: slot 0 mid-prefill (2 of many tokens); row 1: slot 1
            # finishing (prompt complete this chunk)
            for j, (slot, toks, fin) in enumerate(
                ((0, [5, 9], 0), (1, [3, 7, 2], 1))
            ):
                pack[j, : len(toks)] = toks
                pack[j, 8] = 0
                pack[j, 8 + 1] = len(toks)
                pack[j, 8 + 2] = np.float32(0.0).view(np.int32)
                meta[0, j], meta[1, j] = slot, fin
            stale = jnp.asarray([True, True])  # both slots' flags stale
            out = op(
                eng.params, eng.cache, jnp.zeros((2,), jnp.int32), stale,
                jnp.zeros((2,), jnp.float32), jnp.asarray(pack),
                jnp.asarray(meta), jax.random.PRNGKey(0),
            )
            active = np.asarray(out[5])
            assert active[0] == False  # noqa: E712 — mid-prefill cleared
            assert active[1] == True  # noqa: E712 — finishing activated
        finally:
            eng.close()

    def test_rolling_reused_slot_mid_prefill_stays_exact(self, params_w):
        """Integration net: finish a request (slot flag stale), then
        overlap a long decoder with a multi-chunk prompt in the reused
        slot — tokens must stay equal to the isolated references."""
        eng = LLMEngine(
            CFGW, params_w, slots=2, max_seq_len=96, prefill_buckets=(16,),
            prefill_chunk=16, step_token_budget=16, warmup=False,
        )
        try:
            import threading

            rng = np.random.default_rng(7)
            first = rng.integers(1, CFGW.vocab_size, 4).tolist()
            assert eng.generate(first, max_new_tokens=2) == \
                _reference(params_w, CFGW, first, 2)  # slot now stale
            decoder = rng.integers(1, CFGW.vocab_size, 4).tolist()
            chunky = rng.integers(1, CFGW.vocab_size, 32).tolist()
            wants = [
                _reference(params_w, CFGW, decoder, 24),
                _reference(params_w, CFGW, chunky, 8),
            ]
            outs: list = [None, None]

            def run(i, p, n):
                outs[i] = eng.generate(p, max_new_tokens=n)

            ts = [
                threading.Thread(target=run, args=(0, decoder, 24)),
                threading.Thread(target=run, args=(1, chunky, 8)),
            ]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=120)
            assert outs == wants
        finally:
            eng.close()


class TestPrefixSeeding:
    def test_exact_hit_skips_all_chunks(self, params):
        eng = LLMEngine(
            CFG, params, slots=2, max_seq_len=64, prefill_buckets=(8,),
            prefill_chunk=8, warmup=False, prefix_cache_mb=8.0,
        )
        try:
            prompt = [5, 9, 2]
            want = _reference(params, CFG, prompt, 6)
            assert eng.generate(prompt, max_new_tokens=6) == want
            steps_cold = eng.stats()["steps"]
            assert eng.generate(prompt, max_new_tokens=6) == want
            assert eng.stats()["steps"] == steps_cold  # no chunks ran
            assert eng.stats()["kvcache"]["prefix"]["hits"] == 1
        finally:
            eng.close()

    def test_mid_prompt_hit_skips_shared_chunks(self, params):
        """A prompt whose PREFIX was served before seeds prefill_pos at
        the entry's length: only the unshared tail chunks run, and the
        tokens still match the cold path exactly."""
        eng = LLMEngine(
            CFG, params, slots=2, max_seq_len=64, prefill_buckets=(8,),
            prefill_chunk=8, warmup=False, prefix_cache_mb=8.0,
        )
        try:
            rng = np.random.default_rng(9)
            shared = rng.integers(1, CFG.vocab_size, 16).tolist()
            longer = shared + rng.integers(1, CFG.vocab_size, 8).tolist()
            want = _reference(params, CFG, longer, 6)
            assert eng.generate(shared, max_new_tokens=2) == \
                _reference(params, CFG, shared, 2)
            steps_seed = eng.stats()["steps"]
            assert eng.generate(longer, max_new_tokens=6) == want
            s = eng.stats()
            assert s["kvcache"]["prefix"]["partial_hits"] == 1
            # 16 shared tokens skipped: the 24-token prompt needed only
            # the 8-token tail chunk (1 step), not 3
            assert s["steps"] - steps_seed == 1
        finally:
            eng.close()

    def test_entry_rows_trimmed_to_prompt_length(self, params):
        """The append scatter never writes padding rows, so a finished
        prompt's prefix entry retains exactly len(prompt) rows — not the
        chunk-padded count, which would bill garbage against the byte
        budget and evict live entries early."""
        eng = LLMEngine(
            CFG, params, slots=2, max_seq_len=64, prefill_buckets=(8,),
            prefill_chunk=8, warmup=False, prefix_cache_mb=8.0,
            kv_paged=False,  # pins PrefixCache row-trim accounting
        )
        try:
            prompt = list(range(1, 10))  # 9 tokens straddle the 8-chunk
            eng.generate(prompt, max_new_tokens=2)
            e, exact = eng.kv.prefix.lookup_longest(prompt)
            assert exact and e.k.shape[2] == len(prompt)
            eng.kv.prefix.release(e)
        finally:
            eng.close()

    def test_rolling_engine_skips_partial_probe(self, params_w):
        """Rolling layouts can't consume mid-prompt seeds, so the cache
        must not count/pin partial hits the engine would discard."""
        eng = LLMEngine(
            CFGW, params_w, slots=2, max_seq_len=64, prefill_buckets=(16,),
            prefill_chunk=16, warmup=False, prefix_cache_mb=8.0,
            kv_paged=False,  # pins the rolling layout's partial-probe skip
        )
        try:
            shared = list(range(1, 18))
            eng.generate(shared, max_new_tokens=2)
            ext = shared + [30, 31]
            assert eng.generate(ext, max_new_tokens=4) == \
                _reference(params_w, CFGW, ext, 4)
            ps = eng.stats()["kvcache"]["prefix"]
            assert ps["partial_hits"] == 0
        finally:
            eng.close()

    def test_partial_hit_cold_equivalence_under_eviction_pressure(self, params):
        """Partial seeding with a thrashing cache stays exact."""
        eng = LLMEngine(
            CFG, params, slots=2, max_seq_len=64, prefill_buckets=(8,),
            prefill_chunk=8, warmup=False, prefix_cache_mb=0.02,
        )
        try:
            rng = np.random.default_rng(3)
            base = rng.integers(1, CFG.vocab_size, 8).tolist()
            for i in range(4):
                longer = base + rng.integers(1, CFG.vocab_size, 4 + i).tolist()
                assert eng.generate(longer, max_new_tokens=4) == \
                    _reference(params, CFG, longer, 4)
        finally:
            eng.close()


class TestPrefixCacheLookupLongest:
    def test_longest_stored_prefix_wins(self):
        from gofr_tpu.kvcache import PrefixCache

        pc = PrefixCache(capacity_bytes=1 << 20)
        rows = np.zeros(64, np.int8)
        pc.put(PrefixCache.key_for([1, 2]), rows, rows, 2, rows)
        pc.put(PrefixCache.key_for([1, 2, 3, 4]), rows, rows, 4, rows)
        e, exact = pc.lookup_longest([1, 2, 3, 4, 5, 6])
        assert e is not None and not exact and e.length == 4
        pc.release(e)
        e, exact = pc.lookup_longest([1, 2, 3, 4])
        assert e is not None and exact and e.length == 4
        pc.release(e)
        e, exact = pc.lookup_longest([9, 9])
        assert e is None and not exact
        assert pc.stats()["partial_hits"] == 1


@pytest.mark.slow
class TestExhaustiveEquality:
    """Boundary sweep: every prompt length through two chunk geometries,
    chunked vs monolithic vs reference. Slow-marked — CI's full run
    covers it, tier-1 skips."""

    def test_dense_sweep(self, params):
        chunked, mono = _engines(
            CFG, params, slots=4, max_seq_len=64, prefill_buckets=(8, 16),
            step_token_budget=20, prefill_chunk=8,
        )
        try:
            for plen in range(1, 33):
                rng = np.random.default_rng(1000 + plen)
                prompt = rng.integers(1, CFG.vocab_size, plen).tolist()
                want = _reference(params, CFG, prompt, 6)
                assert mono.generate(prompt, max_new_tokens=6) == want, plen
                assert chunked.generate(prompt, max_new_tokens=6) == want, plen
        finally:
            chunked.close()
            mono.close()

    def test_rolling_sweep(self, params_w):
        chunked, mono = _engines(
            CFGW, params_w, slots=2, max_seq_len=64, prefill_buckets=(16,),
            step_token_budget=32, prefill_chunk=16,
        )
        try:
            for plen in range(1, 33, 2):
                rng = np.random.default_rng(2000 + plen)
                prompt = rng.integers(1, CFGW.vocab_size, plen).tolist()
                want = _reference(params_w, CFGW, prompt, 8)
                assert mono.generate(prompt, max_new_tokens=8) == want, plen
                assert chunked.generate(prompt, max_new_tokens=8) == want, plen
        finally:
            chunked.close()
            mono.close()


class TestCollectorJumpSafety:
    """The collector's TTFT priority-jump must never reorder an active
    request's stream: a step entry's piggybacked decode chunk carries
    tokens for already-active slots whose EARLIER tokens may sit in the
    bypassed entries (a prefill wave carries only fresh first tokens, so
    it always jumps)."""

    @staticmethod
    def _step_entry(finishes, snapshot, k=8):
        # ("step", first_dev, finishes, toks_dev, snapshot, K, info)
        return ("step", None, finishes, None, snapshot, k, {})

    def test_prefill_always_jumps(self):
        assert LLMEngine._jump_safe(("prefill", None, [], {}))

    def test_step_with_only_finishing_rows_jumps(self):
        r = GenRequest([1, 2], max_new_tokens=4)
        e = self._step_entry([(0, 1, r)], [None, r, None])
        assert LLMEngine._jump_safe(e)

    def test_step_carrying_active_decode_stays_fifo(self):
        """An active (non-finishing) snapshot row has earlier tokens in
        flight — jumping would emit its later chunk first."""
        fresh = GenRequest([1, 2], max_new_tokens=4)
        active = GenRequest([3, 4], max_new_tokens=16)
        e = self._step_entry([(0, 1, fresh)], [active, fresh])
        assert not LLMEngine._jump_safe(e)

    def test_step_without_finishes_never_jumps(self):
        active = GenRequest([3, 4], max_new_tokens=16)
        assert not LLMEngine._jump_safe(self._step_entry([], [active, None]))

    def test_chunk_never_jumps(self):
        assert not LLMEngine._jump_safe(("chunk", None, [None], 8, {}))


class TestPrefixLengthIndex:
    def test_lengths_track_puts_evictions_and_clear(self):
        """lookup_longest probes the refcounted distinct-length index
        (rebuilding it by scanning every entry put an O(entries) walk on
        the scheduler thread per exact-miss admission)."""
        from gofr_tpu.kvcache import PrefixCache

        rows = np.zeros(512, np.int8)
        pc = PrefixCache(capacity_bytes=3 * 3 * rows.nbytes + 1)
        for i, length in enumerate((2, 2, 4)):
            pc.put(PrefixCache.key_for([i, 0, 7]), rows, rows, length, rows)
        assert dict(pc._lengths) == {2: 2, 4: 1}
        # one more put exceeds the 3-entry budget: LRU evicts a length-2
        pc.put(PrefixCache.key_for([9, 9, 9]), rows, rows, 6, rows)
        assert dict(pc._lengths) == {2: 1, 4: 1, 6: 1}
        # the index drives lookup_longest exactly like an entry scan did
        pc.put(PrefixCache.key_for([1, 2]), rows, rows, 2, rows)
        e, exact = pc.lookup_longest([1, 2, 3])
        assert e is not None and not exact and e.length == 2
        pc.release(e)
        pc.clear()
        assert not pc._lengths and not pc._entries


class TestTokenWeightedRouting:
    def test_pick_prefers_token_light_replica(self, params):
        """A 63-token prompt must outweigh several 2-token prompts: the
        router reads queued TOKENS, not request count."""
        from gofr_tpu.llm import ReplicatedLLMEngine

        eng = ReplicatedLLMEngine(
            CFG, params, replicas=2, slots=2, max_seq_len=128,
            prefill_buckets=(8,), warmup=False,
        )
        try:
            a, b = eng.engines
            # manufacture imbalance: replica a owes one big request
            big = GenRequest(list(range(1, 64)), max_new_tokens=32)
            with a._lock:
                big._load_acct = 63 + 32
                a._load_tokens += big._load_acct
            try:
                assert a.load_tokens() == 95 and b.load_tokens() == 0
                # several tiny requests' worth of count on b — the
                # count-based router would now pick a; tokens pick b
                for _ in range(3):
                    small = GenRequest([1, 2], max_new_tokens=2)
                    with b._lock:
                        small._load_acct = 4
                        b._load_tokens += 4
                assert b.load_tokens() == 12
                assert eng._pick() is b
            finally:
                with a._lock:
                    a._load_tokens = 0
                with b._lock:
                    b._load_tokens = 0
        finally:
            eng.close()

    def test_load_tokens_drains_to_zero(self, params):
        eng = LLMEngine(
            CFG, params, slots=2, max_seq_len=64, prefill_buckets=(8,),
            warmup=False,
        )
        try:
            assert eng.load_tokens() == 0
            eng.generate([5, 9, 2], max_new_tokens=6)
            assert eng.load_tokens() == 0  # fully credited back
        finally:
            eng.close()


class TestAdmissionFailureRecovery:
    """A transient device error during admission must not strand requests:
    anything sliced out of _waiting but never slotted goes back to the
    head of the queue (llm.py _requeue_stranded), so the next scheduler
    pass retries it instead of its consumer hanging to the stream
    timeout."""

    def test_wave_prefill_failure_requeues_and_retries(self, params):
        eng = LLMEngine(
            CFG, params, slots=2, max_seq_len=64, prefill_buckets=(8,),
            step_token_budget=0, warmup=False,
        )
        try:
            real, boom = eng._prefill_op, {"left": 1}

            def flaky(*a, **k):
                if boom["left"]:
                    boom["left"] -= 1
                    raise RuntimeError("injected transient device failure")
                return real(*a, **k)

            eng._prefill_op = flaky
            prompt = [5, 9, 2]
            req = eng.submit(GenRequest(prompt, max_new_tokens=4))
            toks = req.tokens(timeout=30)  # hangs here without the requeue
            assert toks == _reference(params, CFG, prompt, 4)
            assert req.finish_reason == "length"
            assert boom["left"] == 0  # the failure really fired
            assert eng.stats()["waiting"] == 0 and eng._admitting == 0
        finally:
            eng.close()

    def test_chunked_exact_hit_failure_requeues_and_retries(self, params):
        eng = LLMEngine(
            CFG, params, slots=2, max_seq_len=64, prefill_buckets=(8,),
            prefill_chunk=8, step_token_budget=16, prefix_cache_mb=4,
            warmup=False, kv_paged=False,  # wedges PrefixCache.assemble
        )
        try:
            prompt = [7, 3, 1, 4]
            want = eng.generate(prompt, max_new_tokens=4)  # stores the entry
            real, boom = eng.kv.prefix.assemble, {"left": 1}

            def flaky(*a, **k):
                if boom["left"]:
                    boom["left"] -= 1
                    raise RuntimeError("injected transient device failure")
                return real(*a, **k)

            eng.kv.prefix.assemble = flaky
            req = eng.submit(GenRequest(prompt, max_new_tokens=4))
            assert req.tokens(timeout=30) == want
            assert boom["left"] == 0
            # a fresh (miss) prompt still flows after the recovery
            other = [2, 8]
            assert eng.generate(other, max_new_tokens=3) == _reference(
                params, CFG, other, 3
            )
        finally:
            eng.close()

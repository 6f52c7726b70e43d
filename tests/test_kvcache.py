"""KV-cache subsystem tests (gofr_tpu.kvcache).

Load-bearing invariants:
- A window-bounded ROLLING slot cache must emit exactly the tokens the
  dense path emits — the ring is a memory layout, never a model change —
  for prompts both shorter and longer than the window.
- A prefix-cache HIT must reproduce the uncached token stream exactly
  (greedy), while skipping the prefill wave.
- Refcounting pins entries against eviction; LRU eviction enforces the
  byte budget; all of it is observable via stats() and the metrics
  manager.
- At max_seq_len >> window the slot cache's row axis (and byte cost) is
  bounded by the window, not the sequence budget.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.kvcache import CacheManager, PrefixCache, ring_pack
from gofr_tpu.llm import GenRequest, LLMEngine
from gofr_tpu.models import TransformerConfig, generate, init_params
from gofr_tpu.models.transformer import prefill
from gofr_tpu.ops import ring_positions

CFG = TransformerConfig.tiny()
CFGW = TransformerConfig.tiny_mistral()  # sliding window 8


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def params_w():
    return init_params(jax.random.PRNGKey(3), CFGW)


def _reference(params, cfg, prompt: list[int], n: int) -> list[int]:
    toks = jnp.asarray([prompt], jnp.int32)
    lens = jnp.asarray([len(prompt)], jnp.int32)
    return [int(t) for t in np.asarray(generate(params, cfg, toks, lens, n))[0]]


class TestRingGeometry:
    def test_ring_positions_matches_oracle(self):
        C = 16
        lengths = jnp.asarray([0, 1, 5, 16, 23], jnp.int32)
        got = np.asarray(ring_positions(lengths, C))
        for b, t in enumerate([0, 1, 5, 16, 23]):
            # oracle: replay the writes — position p lands at row p mod C,
            # so each row ends up holding the newest position it ever saw
            rows = [-1] * C
            for p in range(t):
                rows[p % C] = p
            got_b = [int(v) if v >= 0 else -1 for v in got[b]]
            assert got_b == rows, (t, got[b], rows)

    def test_ring_requires_window(self):
        from gofr_tpu.ops import decode_attention

        q = jnp.zeros((1, 1, 2, 4))
        kc = jnp.zeros((1, 8, 1, 4))
        with pytest.raises(ValueError, match="ring"):
            decode_attention(q, kc, kc, jnp.asarray([4]), window=0, ring=8)


class TestRingPack:
    @pytest.mark.parametrize("plen", [5, 20])  # shorter & longer than C=16
    def test_pack_keeps_newest_rows(self, params_w, plen):
        C = 16
        rng = np.random.default_rng(7)
        prompt = rng.integers(1, CFGW.vocab_size, plen).tolist()
        toks = jnp.asarray([prompt], jnp.int32)
        lens = jnp.asarray([plen], jnp.int32)
        _, dense = prefill(params_w, CFGW, toks, lens, plen)
        packed = ring_pack(dense, C)
        dk, pk = np.asarray(dense.k), np.asarray(packed.k)
        assert pk.shape[2] == C
        for j in range(C):
            rows = [p for p in range(plen) if p % C == j]
            if rows:
                np.testing.assert_array_equal(pk[:, 0, j], dk[:, 0, rows[-1]])
            else:
                assert (pk[:, 0, j] == 0).all()  # never-written rows zeroed


class TestRollingEngine:
    # kv_paged=False throughout: these tests pin the CONTIGUOUS layouts
    # (rolling ring vs dense slab), kept as the paged pool's A/B lever —
    # paged engines are pinned against them in tests/test_paged_kv.py
    @pytest.fixture(scope="class")
    def engines(self, params_w):
        rolling = LLMEngine(
            CFGW, params_w, slots=2, max_seq_len=64, prefill_buckets=(16, 32),
            warmup=False, kv_paged=False,
        )
        dense = LLMEngine(
            CFGW, params_w, slots=2, max_seq_len=64, prefill_buckets=(16, 32),
            warmup=False, kv_paged=False,
            kv_window=0,  # force the dense slab (A/B lever)
        )
        yield rolling, dense
        rolling.close()
        dense.close()

    def test_layouts(self, engines):
        rolling, dense = engines
        assert rolling.kv.stats()["layout"] == "rolling"
        # ring capacity = window + max(decode_chunk, largest prefill-chunk
        # shape): a chunk append must never overwrite an in-window row
        assert rolling.cache.k.shape[2] == rolling.kv.capacity
        assert rolling.kv.capacity == 8 + max(8, max(rolling.chunk_shapes))
        assert dense.kv.stats()["layout"] == "dense"
        # max_seq_len + the two decode chunks of merge slack a slot holds
        assert dense.cache.k.shape[2] == dense.kv.capacity == 64 + 2 * 8

    @pytest.mark.parametrize("plen", [4, 20, 30])  # straddle the window (8)
    def test_rolling_matches_dense_and_reference(self, engines, params_w, plen):
        rolling, dense = engines
        rng = np.random.default_rng(plen)
        prompt = rng.integers(1, CFGW.vocab_size, plen).tolist()
        want = _reference(params_w, CFGW, prompt, 10)
        assert rolling.generate(prompt, max_new_tokens=10) == want
        assert dense.generate(prompt, max_new_tokens=10) == want

    def test_memory_bounded_by_window_at_long_max_len(self, params_w):
        """max_seq_len >> window: the slot cache's row axis (hence bytes)
        stays at window + chunk; long prompts still decode exactly."""
        eng = LLMEngine(
            CFGW, params_w, slots=2, max_seq_len=256, prefill_buckets=(128,),
            prefill_chunk=16, warmup=False,  # chunk shape caps the ring slack
            kv_paged=False,
        )
        try:
            kv = eng.kv.stats()
            assert kv["capacity"] == 8 + max(eng.decode_chunk, 16) < 256
            assert eng.cache.k.shape[2] == kv["capacity"]
            # bytes scale with capacity, not max_seq_len
            dense_bytes = kv["slot_bytes"] * 256 // kv["capacity"]
            assert kv["slot_bytes"] * 8 < dense_bytes
            rng = np.random.default_rng(11)
            prompt = rng.integers(1, CFGW.vocab_size, 100).tolist()
            got = eng.generate(prompt, max_new_tokens=8)
            assert got == _reference(params_w, CFGW, prompt, 8)
        finally:
            eng.close()


def _fake_rows(nbytes: int):
    """numpy stand-ins for device KV rows (PrefixCache only reads .nbytes)."""
    k = np.zeros(max(1, nbytes // 3), np.int8)
    return k, k, np.zeros(nbytes - 2 * k.nbytes, np.int8)


class TestPrefixCacheUnit:
    def test_hit_miss_lru_and_bytes(self):
        pc = PrefixCache(capacity_bytes=300)
        for i in range(3):
            k, v, lg = _fake_rows(100)
            assert pc.put(bytes([i]), k, v, 4, lg)
        assert pc.resident_bytes == 300
        assert pc.lookup(bytes([9])) is None  # miss
        e0 = pc.lookup(bytes([0]))  # hit: entry 0 becomes MRU, pinned
        assert e0 is not None
        pc.release(e0)
        k, v, lg = _fake_rows(100)
        assert pc.put(bytes([3]), k, v, 4, lg)
        s = pc.stats()
        # LRU victim is entry 1 (0 was touched), budget holds at 300
        assert s["evictions"] == 1 and s["resident_bytes"] == 300
        assert pc.lookup(bytes([1])) is None
        assert pc.lookup(bytes([0])) is not None

    def test_pinned_entries_survive_eviction(self):
        pc = PrefixCache(capacity_bytes=250)
        k, v, lg = _fake_rows(100)
        pc.put(b"a", k, v, 1, lg)
        pinned = pc.lookup(b"a")  # refs = 1
        for key in (b"b", b"c"):
            k, v, lg = _fake_rows(100)
            pc.put(key, k, v, 1, lg)
        # over budget: b (oldest unpinned) was evicted, a survived its turn
        assert pc.lookup(b"a") is not None
        assert pc.lookup(b"b") is None
        pc.release(pinned)

    def test_oversized_and_duplicate_refused(self):
        pc = PrefixCache(capacity_bytes=100)
        k, v, lg = _fake_rows(101)
        assert not pc.put(b"big", k, v, 1, lg)  # would evict everything
        k, v, lg = _fake_rows(50)
        assert pc.put(b"x", k, v, 1, lg)
        assert not pc.put(b"x", k, v, 1, lg)  # duplicate key
        assert pc.stats()["stores"] == 1

    def test_key_is_exact_token_content(self):
        assert PrefixCache.key_for([1, 2, 3]) == PrefixCache.key_for((1, 2, 3))
        assert PrefixCache.key_for([1, 2, 3]) != PrefixCache.key_for([1, 2])
        assert PrefixCache.key_for([1, 2, 3]) != PrefixCache.key_for([3, 2, 1])


class TestPrefixEngine:
    # kv_paged=False: these pin the contiguous whole-row PrefixCache
    # (byte formulas, wave accounting); the paged radix equivalents live
    # in tests/test_paged_kv.py / tests/test_sessions.py
    def test_cached_matches_uncached_and_skips_prefill(self, params):
        from gofr_tpu.metrics import new_metrics_manager

        metrics = new_metrics_manager()
        eng = LLMEngine(
            CFG, params, slots=4, max_seq_len=64, prefill_buckets=(8, 16),
            warmup=False, prefix_cache_mb=8.0, metrics=metrics,
            kv_paged=False,
        )
        plain = LLMEngine(
            CFG, params, slots=4, max_seq_len=64, prefill_buckets=(8, 16),
            warmup=False, kv_paged=False,
        )
        try:
            prompt = [5, 9, 2]
            want = plain.generate(prompt, max_new_tokens=6)
            cold = eng.generate(prompt, max_new_tokens=6)
            warm = eng.generate(prompt, max_new_tokens=6)
            assert cold == want and warm == want
            kv = eng.stats()["kvcache"]["prefix"]
            assert kv["hits"] == 1 and kv["misses"] == 1 and kv["stores"] == 1
            # rows are stored trimmed to the prompt's exact length (the
            # append scatter never writes padding rows), not the 64-row
            # slab — the budget buys prefixes, not padding
            row_bytes = (
                2 * CFG.n_layers * len(prompt) * CFG.n_kv_heads
                * CFG.head_dim * 4
            )
            logit_bytes = CFG.vocab_size * 4
            assert kv["resident_bytes"] == row_bytes + logit_bytes
            # a hit dispatches no prefill: the miss ran unified steps, the
            # hit added none (chunked scheduler; waves only serve hits)
            s = eng.stats()
            assert s["scheduler"] == "chunked" and s["steps"] >= 1
            assert s["wave_reqs"] == 0
            # metrics-server visibility (Prometheus exposition)
            text = metrics.render_prometheus()
            assert 'app_kvcache_events{event="hit"' in text
            assert 'app_kvcache_resident_bytes{kind="prefix"' in text
            assert 'kind="slots"' in text
        finally:
            eng.close()
            plain.close()

    def test_eviction_under_pressure_keeps_serving(self, params):
        """A budget that holds ~3 entries (rows are stored trimmed to the
        8-token bucket, ~6 KB each): cycle 6 prompts twice; LRU thrashes,
        evictions fire, and every completion stays correct."""
        eng = LLMEngine(
            CFG, params, slots=2, max_seq_len=64, prefill_buckets=(8,),
            warmup=False, prefix_cache_mb=0.02, kv_paged=False,
        )
        try:
            prompts = [[i + 1, i + 2, i + 3] for i in range(6)]
            wants = [_reference(params, CFG, p, 4) for p in prompts]
            for _round in range(2):
                for p, want in zip(prompts, wants):
                    assert eng.generate(p, max_new_tokens=4) == want
            s = eng.stats()["kvcache"]["prefix"]
            assert s["evictions"] > 0
            assert s["resident_bytes"] <= s["capacity_bytes"]
        finally:
            eng.close()

    def test_sampled_hits_draw_from_cached_logits(self, params):
        """temperature > 0 on a hit: valid ids, right count (distribution
        comes from the stored logits; determinism is a greedy property)."""
        eng = LLMEngine(
            CFG, params, slots=2, max_seq_len=64, prefill_buckets=(8,),
            warmup=False, prefix_cache_mb=8.0, kv_paged=False,
        )
        try:
            eng.generate([4, 4, 4], max_new_tokens=4)  # seed the cache
            out = eng.submit(
                GenRequest([4, 4, 4], max_new_tokens=4, temperature=1.2)
            ).tokens()
            assert len(out) == 4
            assert all(0 <= t < CFG.vocab_size for t in out)
            assert eng.stats()["kvcache"]["prefix"]["hits"] == 1
        finally:
            eng.close()

    def test_rolling_engine_with_prefix_cache(self, params_w):
        """Ring rows round-trip through the prefix cache: a hit on a
        windowed config reproduces the uncached stream exactly."""
        eng = LLMEngine(
            CFGW, params_w, slots=2, max_seq_len=64, prefill_buckets=(16, 32),
            warmup=False, prefix_cache_mb=8.0, kv_paged=False,
        )
        try:
            rng = np.random.default_rng(5)
            prompt = rng.integers(1, CFGW.vocab_size, 20).tolist()
            want = _reference(params_w, CFGW, prompt, 8)
            assert eng.generate(prompt, max_new_tokens=8) == want
            assert eng.generate(prompt, max_new_tokens=8) == want
            assert eng.stats()["kvcache"]["prefix"]["hits"] == 1
        finally:
            eng.close()


class TestManagerPlanning:
    def test_dense_when_window_absent_or_too_wide(self):
        assert not CacheManager(CFG, 2, 64, 8).rolling
        # window + chunk >= max_seq_len: rolling buys nothing
        assert not CacheManager(CFGW, 2, 16, 8).rolling
        assert CacheManager(CFGW, 2, 64, 8).rolling

    def test_window_override_must_match_config(self):
        with pytest.raises(ValueError, match="sliding_window"):
            CacheManager(CFGW, 2, 64, 8, window=4)

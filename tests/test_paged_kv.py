"""Paged KV block pool tests (gofr_tpu.kvcache.paged).

Load-bearing invariants:
- **COW**: no write ever lands in a block with refcount > 1 — enforced
  mechanically by BlockPool.ensure_writable and by construction in the
  engine (shared radix blocks sit strictly below every writer's cursor;
  partial tails are shared by copy). Property-tested over randomized
  op sequences.
- **Radix**: insert/split/evict keep the trie consistent (block-aligned
  edges, group-keyed children, refcounted block ownership) and lookup
  returns the longest block-aligned shared prefix.
- **Spill -> restore** round-trips device blocks byte-identically
  through the host tier.
- **Pool exhaustion** queues admissions; it never crashes or corrupts.
- **paged == contiguous**: greedy token-identity across dense, rolling
  (windowed), prefix-hit, chunked, and speculative paths — the pool is
  a memory layout, never a model change.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.kvcache import CacheManager
from gofr_tpu.kvcache.paged import (
    BlockPool,
    PoolExhausted,
    RadixTree,
    gather_blocks_host,
    gather_slots,
    quantize_rows,
    scatter_rows,
    stored_rows,
)
from gofr_tpu.llm import GenRequest, LLMEngine
from gofr_tpu.models import TransformerConfig, generate, init_params

CFG = TransformerConfig.tiny()
CFGW = TransformerConfig.tiny_mistral()  # sliding window 8
B = 4  # unit-test block size


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def params_w():
    return init_params(jax.random.PRNGKey(3), CFGW)


def _reference(params, cfg, prompt, n):
    toks = jnp.asarray([prompt], jnp.int32)
    lens = jnp.asarray([len(prompt)], jnp.int32)
    return [int(t) for t in np.asarray(generate(params, cfg, toks, lens, n))[0]]


class TestBlockPool:
    def test_alloc_free_refcount(self):
        pool = BlockPool(8, B, 100)
        a = pool.alloc(3)
        assert pool.blocks_in_use() == 3 and pool.available() == 5
        pool.incref(a[:2])
        assert pool.blocks_shared() == 2
        assert pool.decref(a) == 1  # only the unshared block frees
        assert pool.blocks_in_use() == 2
        pool.decref(a[:2])
        assert pool.blocks_in_use() == 0

    def test_reservation_gates_allocation(self):
        pool = BlockPool(4, B, 100)
        assert pool.reserve(3)
        assert not pool.reserve(2)  # only 1 unreserved left
        pool.alloc(2, reserved=True)
        assert pool.reserved == 1
        with pytest.raises(PoolExhausted):
            pool.alloc(2)  # 2 free, but 1 is promised
        pool.unreserve(1)
        pool.alloc(2)

    def test_cow_never_writes_shared(self):
        """The mechanical COW invariant: ensure_writable returns a COPY
        target whenever the block is shared, and the writer's reference
        migrates — the shared block's other readers keep their count."""
        pool = BlockPool(8, B, 100)
        (b,) = pool.alloc(1)
        assert pool.ensure_writable(b) is None  # private: write in place
        pool.incref([b])  # now shared
        fresh = pool.ensure_writable(b)
        assert fresh is not None and fresh != b
        assert pool.refs[b] == 1 and pool.refs[fresh] == 1
        assert pool.cow_copies == 1

    def test_property_no_write_into_shared(self):
        """Randomized op sequence: every write goes through
        ensure_writable first; assert no write target ever has
        refcount > 1 at write time, and refcounts never go negative."""
        rng = np.random.default_rng(0)
        pool = BlockPool(32, B, 100)
        owned: list[int] = []  # writer-owned blocks
        shared: list[int] = []  # blocks with an extra reader ref
        writes = 0
        for _ in range(800):
            op = rng.integers(0, 5)
            if op == 0 and pool.available() > 0:
                owned.extend(pool.alloc(1))
            elif op == 1 and owned:
                b = owned[rng.integers(len(owned))]
                pool.incref([b])
                shared.append(b)
            elif op == 2 and shared:
                b = shared.pop(rng.integers(len(shared)))
                pool.decref([b])
            elif op == 3 and owned:
                i = int(rng.integers(len(owned)))
                if pool.refs[owned[i]] > 1 and pool.available() == 0:
                    continue  # COW impossible: a real writer evicts first
                tgt = pool.ensure_writable(owned[i])
                if tgt is not None:
                    owned[i] = tgt  # COW: repoint before writing
                assert pool.refs[owned[i]] == 1  # THE invariant
                writes += 1
            elif op == 4 and owned:
                b = owned.pop(rng.integers(len(owned)))
                pool.decref([b])  # writer retires
            assert (pool.refs >= 0).all()
        assert writes > 50  # the property was actually exercised

    def test_write_into_free_block_rejected(self):
        pool = BlockPool(4, B, 100)
        (b,) = pool.alloc(1)
        pool.decref([b])
        with pytest.raises(ValueError, match="free block"):
            pool.ensure_writable(b)


class TestRadixTree:
    def _tree(self, n_blocks=64):
        pool = BlockPool(n_blocks, B, 100)
        return pool, RadixTree(pool, B, 0)

    def test_insert_lookup_longest_block_prefix(self):
        pool, tree = self._tree()
        p1 = list(range(10))  # 2 full blocks + 2-token tail
        b1 = pool.alloc(2)
        tree.insert(p1, b1)
        m = tree.lookup(list(range(8)) + [77, 78, 79])
        assert m.shared == 8 and m.blocks == b1  # both blocks shared
        m = tree.lookup(list(range(4)) + [77, 78, 79, 80])
        assert m.shared == 4 and m.blocks == b1[:1]  # mid-edge partial
        m = tree.lookup([77] * 8)
        assert m.shared == 0 and m.blocks == []

    def test_split_preserves_both_paths(self):
        pool, tree = self._tree()
        b1 = pool.alloc(3)
        tree.insert(list(range(12)), b1)
        # diverge after block 1 -> edge split at the block boundary
        b2 = pool.alloc(1)
        p2 = list(range(4)) + [50, 51, 52, 53]
        m = tree.lookup(p2)
        tree.insert(p2, m.blocks + b2)
        assert tree.lookup(list(range(12))).shared == 12
        assert tree.lookup(p2).shared == 8
        # the shared first block now carries radix refs from the split
        assert pool.refs[b1[0]] >= 1
        # divergence INSIDE a block shares nothing (sub-block granularity
        # is not representable; children are keyed by whole groups)
        m = tree.lookup([0, 1, 2, 99] + [50, 51, 52, 53])
        assert m.shared == 0

    def test_exact_end_record_and_tail(self):
        pool, tree = self._tree()
        blocks = pool.alloc(2)
        tail = pool.alloc(1)[0]
        tree.insert(
            [1, 2, 3, 4, 5, 6, 7, 8, 9], blocks,
            tail_block=tail, tail_len=1, logits="LG", logits_nbytes=4,
        )
        m = tree.lookup([1, 2, 3, 4, 5, 6, 7, 8, 9])
        assert m.end is not None and m.end.logits == "LG"
        assert m.end.tail_block == tail and m.end.tail_len == 1
        # one token longer: not exact, shares the full blocks
        m = tree.lookup([1, 2, 3, 4, 5, 6, 7, 8, 9, 10])
        assert m.end is None and m.shared == 8

    def test_evict_lru_leaves_and_refcounts(self):
        pool, tree = self._tree()
        b1, b2 = pool.alloc(1), pool.alloc(1)
        n1, _ = tree.insert([1, 2, 3, 4], b1)
        tree.insert([9, 8, 7, 6], b2)
        tree.lookup([1, 2, 3, 4])  # touch: n1 becomes MRU
        tree.pin(n1)
        freed = tree.evict_for(2)
        # the unpinned leaf went; the pinned one survived
        assert tree.lookup([1, 2, 3, 4]).shared == 4
        assert tree.lookup([9, 8, 7, 6]).shared == 0
        assert freed == 0 or pool.refs[b2[0]] == 1  # writer ref remains
        tree.unpin(n1)
        tree.evict_for(2)
        assert tree.nodes == 0

    def test_insert_dedups_existing_prefix(self):
        """Two identical prompts published independently: the second
        publish adopts the FIRST's blocks; its own stay writer-owned."""
        pool, tree = self._tree()
        b1 = pool.alloc(1)
        b2 = pool.alloc(1)
        tree.insert([1, 2, 3, 4], b1)
        tree.insert([1, 2, 3, 4], b2)
        assert pool.refs[b1[0]] == 2  # writer + radix
        assert pool.refs[b2[0]] == 1  # writer only — deduplicated away


class TestDeviceHelpers:
    def test_gather_reconstructs_contiguous(self):
        rng = np.random.default_rng(1)
        L, NB, hkv, hd, S, MB = 2, 10, 2, 4, 3, 2
        # the pool as stored: a row flat, head h the columns [h * hd, (h + 1) * hd)
        pk = jnp.asarray(rng.normal(size=(L, NB, B, hkv * hd)).astype(np.float32))
        pv = jnp.asarray(rng.normal(size=(L, NB, B, hkv * hd)).astype(np.float32))
        tables = jnp.asarray(rng.integers(0, NB, (S, MB)).astype(np.int32))
        lens = jnp.asarray([3, 8, 0], jnp.int32)
        c = gather_slots(pk, pv, tables, lens, rows=((hkv, hd), (hkv, hd)))
        assert c.k.shape == (L, S, MB * B, hkv, hd)
        t = np.asarray(tables)
        for s in range(S):
            for p in range(MB * B):
                np.testing.assert_array_equal(
                    np.asarray(c.k)[:, s, p],
                    np.asarray(pk)[:, t[s, p // B], p % B].reshape(L, hkv, hd),
                )

    def test_scatter_respects_valid_mask(self):
        L, NB, hkv, hd, S, W = 1, 6, 1, 2, 2, 3
        pk = jnp.zeros((L, NB, B, hkv * hd))
        pv = jnp.zeros((L, NB, B, hkv * hd))
        tables = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
        rows = jnp.ones((L, S, W, hkv, hd))
        pos = jnp.asarray([[0, 1, 2], [4, 5, 6]], jnp.int32)
        valid = jnp.asarray([[True, True, False], [True, False, True]])
        k2, _, _ = scatter_rows(pk, pv, tables, rows, rows, pos, valid)
        k2 = np.asarray(k2)
        assert k2[0, 0, 0].any() and k2[0, 0, 1].any() and not k2[0, 0, 2].any()
        assert k2[0, 3, 0].any() and not k2[0, 3, 1].any() and k2[0, 3, 2].any()
        assert not k2[0, 1].any() and not k2[0, 2].any()  # untouched blocks

    def test_int8_roundtrip_close(self):
        rng = np.random.default_rng(2)
        rows = jnp.asarray(rng.normal(size=(2, 3, 4, 2, 8)).astype(np.float32))
        q, s = quantize_rows(rows)
        back = q.astype(jnp.float32) * s[..., None]
        err = np.abs(np.asarray(back) - np.asarray(rows)).max()
        assert err <= np.abs(np.asarray(rows)).max() / 127 + 1e-6

    def test_spill_restore_byte_identity(self):
        """Device blocks -> host numpy -> device blocks: exact bytes."""
        rng = np.random.default_rng(3)
        L, NB, hkv, hd = 2, 8, 2, 4
        rows = ((hkv, hd), (hkv, hd))
        pk = jnp.asarray(rng.normal(size=(L, NB, B, hkv * hd)).astype(np.float32))
        pv = jnp.asarray(rng.normal(size=(L, NB, B, hkv * hd)).astype(np.float32))
        blocks = [5, 2, 7]
        hk, hv, _ = gather_blocks_host(pk, pv, blocks, rows=rows)
        # outside the process a block keeps its heads, whatever the pool stores
        assert hk.shape == (L, 3, B, hkv, hd)
        np.testing.assert_array_equal(hk.reshape(L, 3, B, -1), np.asarray(pk)[:, blocks])
        # restore into different block ids on a fresh pool
        dst = jnp.asarray([1, 3, 4], jnp.int32)
        nk = jnp.zeros_like(pk).at[:, dst].set(stored_rows(jnp.asarray(hk)))
        rk, _, _ = gather_blocks_host(nk, nk, [1, 3, 4], rows=rows)
        np.testing.assert_array_equal(rk, hk)


class TestManagerPaged:
    def test_layout_and_unified_slack(self):
        kv = CacheManager(
            CFG, 2, 64, 8, paged=True, block=4,
            append_widths=(8, 16, 5),
        )
        assert kv.paged and not kv.rolling and kv.ring == 0
        assert kv.append_slack == 16  # ONE max over every append width
        # 64 rows a request may hold + two decode chunks of the engine's own
        assert kv.slot_rows == 80 and kv.capacity == 80 and kv.table_width == 20
        # contiguous rolling derives its capacity from the SAME term
        kvr = CacheManager(CFGW, 2, 64, 8, append_widths=(8, 16, 5))
        assert kvr.rolling and kvr.capacity == CFGW.sliding_window + 16

    def test_reservation_lifecycle_and_exhaustion(self):
        kv = CacheManager(CFG, 2, 64, 8, paged=True, block=4, pool_blocks=8)
        assert kv.admit_reserve(8, 4, None)  # needs ceil((8+4-1+8)/4)=5
        assert not kv.admit_reserve(8, 4, None)  # 3 unreserved left < 5
        kv.unreserve(kv.reserve_need(8, 4, None))
        assert kv.admit_reserve(8, 4, None)

    def test_seed_plan_pins_blocks_against_eviction(self):
        """Review regression: between lookup_seed and attach_seed, a
        LATER request's reservation in the same admission pass may evict
        the plan's radix leaves — the plan's lookup-time pins must keep
        the blocks alive (and release_plan/attach must not leak them)."""
        kv = CacheManager(
            CFG, 2, 64, 8, paged=True, block=4,
            prefix_cache_mb=1.0, pool_blocks=32,
        )
        assert kv.admit_reserve(8, 4, None)
        kv.attach_seed(0, None, "r0", 8, 4)
        kv.ensure(0, 8)
        pub = kv.publish_plan(0, list(range(8)), want_tail=False)
        kv.publish_commit(pub, list(range(8)))
        kv.release_slot(0, "r0")
        plan = kv.lookup_seed(list(range(8)) + [99])
        assert plan is not None and plan.blocks
        kv.radix.evict_for(10 ** 9)  # the same-pass eviction hazard
        # pinned: blocks alive despite the radix dropping its refs
        assert all(kv.pool.refs[b] >= 1 for b in plan.blocks)
        # attach adopts the pins; retire returns everything
        assert kv.admit_reserve(9, 4, plan)
        kv.attach_seed(1, plan, "r1", 9, 4)
        kv.release_slot(1, "r1")
        assert kv.pool.blocks_in_use() == 0 and kv.pool.reserved == 0
        # and the discard path frees a never-attached plan's pins too
        kv2 = CacheManager(
            CFG, 2, 64, 8, paged=True, block=4,
            prefix_cache_mb=1.0, pool_blocks=32,
        )
        assert kv2.admit_reserve(8, 4, None)
        kv2.attach_seed(0, None, "r0", 8, 4)
        kv2.ensure(0, 8)
        pub = kv2.publish_plan(0, list(range(8)), want_tail=False)
        kv2.publish_commit(pub, list(range(8)))
        kv2.release_slot(0, "r0")
        in_radix = kv2.pool.blocks_in_use()
        plan = kv2.lookup_seed(list(range(8)) + [99])
        kv2.release_plan(plan)
        assert kv2.pool.blocks_in_use() == in_radix  # pin handed back

    def test_release_returns_everything(self):
        kv = CacheManager(CFG, 2, 64, 8, paged=True, block=4, pool_blocks=16)
        assert kv.admit_reserve(8, 4, None)
        kv.attach_seed(0, None, "req", 8, 4)
        kv.ensure(0, 8)
        assert kv.pool.blocks_in_use() == 2
        kv.release_slot(0, "req")
        assert kv.pool.blocks_in_use() == 0 and kv.pool.reserved == 0


class TestPagedEngineEquality:
    """Greedy outputs token-identical paged vs contiguous — pinned
    across dense, rolling/windowed, prefix-hit, chunked and speculative
    layouts (the acceptance-criteria matrix)."""

    def _pair(self, cfg, params, **kw):
        a = LLMEngine(cfg, params, warmup=False, kv_paged=True, **kw)
        b = LLMEngine(cfg, params, warmup=False, kv_paged=False, **kw)
        return a, b

    def test_dense_chunked_and_wave(self, params):
        for budget in (256, 0):  # chunked and monolithic-wave schedulers
            paged, contig = self._pair(
                CFG, params, slots=4, max_seq_len=64,
                prefill_buckets=(8, 16), step_token_budget=budget,
            )
            try:
                rng = np.random.default_rng(7)
                # straddle one block (16) and one chunk boundary; the
                # exhaustive length sweeps live in test_chunked_prefill
                for plen in (3, 17, 33):
                    prompt = rng.integers(1, CFG.vocab_size, plen).tolist()
                    want = _reference(params, CFG, prompt, 8)
                    assert paged.generate(prompt, max_new_tokens=8) == want
                    assert contig.generate(prompt, max_new_tokens=8) == want
                assert paged.kv.stats()["layout"] == "paged"
            finally:
                paged.close()
                contig.close()

    def test_windowed(self, params_w):
        paged, contig = self._pair(
            CFGW, params_w, slots=2, max_seq_len=64, prefill_buckets=(16, 32),
        )
        try:
            rng = np.random.default_rng(8)
            for plen in (4, 30):  # straddle the window (8)
                prompt = rng.integers(1, CFGW.vocab_size, plen).tolist()
                want = _reference(params_w, CFGW, prompt, 10)
                assert paged.generate(prompt, max_new_tokens=10) == want
                assert contig.generate(prompt, max_new_tokens=10) == want
        finally:
            paged.close()
            contig.close()

    def test_prefix_hits_exact_and_block_partial(self, params):
        eng = LLMEngine(
            CFG, params, slots=4, max_seq_len=96, prefill_buckets=(8, 32),
            warmup=False, prefix_cache_mb=4.0,  # paged default: radix
        )
        try:
            rng = np.random.default_rng(9)
            base = rng.integers(1, CFG.vocab_size, 40).tolist()
            want = _reference(params, CFG, base, 6)
            assert eng.generate(base, max_new_tokens=6) == want
            # exact radix hit: skips prefill, reproduces greedily
            assert eng.generate(base, max_new_tokens=6) == want
            st = eng.stats()["kvcache"]["prefix"]
            assert st["hits"] == 1
            # sibling sharing base[:20]: BLOCK-granular partial hit (16
            # tokens at block 16) — the old row cache had no entry for
            # this prompt at all
            sib = base[:20] + rng.integers(1, CFG.vocab_size, 10).tolist()
            assert eng.generate(sib, max_new_tokens=6) == _reference(
                params, CFG, sib, 6
            )
            st = eng.stats()["kvcache"]["prefix"]
            assert st["partial_hits"] >= 1
            # the radix retains the shared prefix blocks (the sibling's
            # slot refs were released at retire; the index persists)
            assert eng.kv.radix.owned_bytes > 0
        finally:
            eng.close()

    def test_speculative(self, params):
        prompt = [5, 6, 7, 5, 6, 7, 5, 6, 7, 5, 6]
        outs = {}
        for paged in (True, False):
            eng = LLMEngine(
                CFG, params, slots=2, max_seq_len=96, decode_chunk=4,
                prefill_buckets=(16,), warmup=False, kv_paged=paged,
                speculative=True, spec_draft=4,
            )
            try:
                outs[paged] = eng.generate(prompt, max_new_tokens=16)
                assert eng.stats()["spec"]["accepted"] > 0  # spec engaged
            finally:
                eng.close()
        assert outs[True] == outs[False]
        # and spec-on == spec-off on the paged layout
        eng = LLMEngine(
            CFG, params, slots=2, max_seq_len=96, decode_chunk=4,
            prefill_buckets=(16,), warmup=False, kv_paged=True,
        )
        try:
            assert eng.generate(prompt, max_new_tokens=16) == outs[True]
        finally:
            eng.close()

    def test_int8_blocks_serve(self, params):
        """int8 KV halves the pool bytes; outputs are sane (quantization
        is lossy by design — no bit-identity claim)."""
        eng = LLMEngine(
            CFG, params, slots=2, max_seq_len=64, prefill_buckets=(16,),
            warmup=False, kv_int8=True,
        )
        try:
            out = eng.generate(list(range(1, 15)), max_new_tokens=8)
            assert len(out) == 8
            assert all(0 <= t < CFG.vocab_size for t in out)
            st = eng.stats()["kvcache"]
            assert st["int8"]
            fp = CacheManager(CFG, 2, 64, 8, paged=True, block=16)
            assert st["block_bytes"] < fp.block_bytes  # int8 + scales < f32
        finally:
            eng.close()


class TestSatisfiedLaneStopsWriting:
    def test_early_finisher_never_outruns_materialized_blocks(self, params):
        """Review regression: chunks driven by a long-running neighbor
        must not advance a SATISFIED slot's device cursor — past the
        materialized watermark its stale table entries may name blocks
        that belong to someone else. Pin: every owned slot's device
        length stays within its materialized blocks while the neighbor
        is still decoding, and both streams are reference-exact."""
        eng = LLMEngine(
            CFG, params, slots=2, max_seq_len=96, decode_chunk=8,
            prefill_buckets=(8,), warmup=False, kv_paged=True,
        )
        try:
            rng = np.random.default_rng(21)
            pa = rng.integers(1, CFG.vocab_size, 6).tolist()
            pb = rng.integers(1, CFG.vocab_size, 6).tolist()
            ra = eng.submit(GenRequest(pa, max_new_tokens=2))
            rb = eng.submit(GenRequest(pb, max_new_tokens=40))
            out_a = ra.tokens(timeout=60)
            # A is done; B keeps driving chunks — sample the invariant
            # a few times while the pipeline is hot
            for _ in range(10):
                with eng._lock:
                    lens = np.asarray(eng.cache.length)
                    for i in range(eng.slots):
                        if eng.kv.slot_owner(i) is None:
                            continue
                        hi_rows = eng.kv._slot_tables[i].hi * eng.kv.block
                        assert int(lens[i]) <= hi_rows, (
                            i, int(lens[i]), hi_rows
                        )
                time.sleep(0.01)
            out_b = rb.tokens(timeout=60)
            assert out_a == _reference(params, CFG, pa, 2)
            assert out_b == _reference(params, CFG, pb, 40)
        finally:
            eng.close()


class TestPoolExhaustion:
    def test_admission_queues_and_completes(self, params):
        """A pool sized for ~1 request at a time: 4 concurrent submits
        all finish correctly — blocked admissions wait for blocks, they
        do not crash, corrupt, or deadlock."""
        eng = LLMEngine(
            CFG, params, slots=4, max_seq_len=64, prefill_buckets=(16,),
            warmup=False, kv_paged=True, kv_pool_blocks=4, kv_block=16,
        )
        try:
            rng = np.random.default_rng(12)
            prompts = [rng.integers(1, CFG.vocab_size, 10).tolist() for _ in range(4)]
            reqs = [
                eng.submit(GenRequest(p, max_new_tokens=4)) for p in prompts
            ]
            outs = [r.tokens(timeout=60) for r in reqs]
            for p, o in zip(prompts, outs):
                assert o == _reference(params, CFG, p, 4)
            # everything returned: no leaked blocks or reservations
            deadline = time.time() + 5
            while time.time() < deadline and eng.kv.pool.blocks_in_use():
                time.sleep(0.05)
            assert eng.kv.pool.blocks_in_use() == 0
            assert eng.kv.pool.reserved == 0
        finally:
            eng.close()

    def test_oversized_request_rejected_not_hung(self, params):
        """A request that can NEVER fit the pool must not hang forever:
        submit-time validation still caps at max_seq_len; the pool cap
        is the admission gate."""
        eng = LLMEngine(
            CFG, params, slots=2, max_seq_len=64, prefill_buckets=(16,),
            warmup=False, kv_paged=True, kv_pool_blocks=8, kv_block=16,
        )
        try:
            # fits: 8 blocks cover one worst-case request
            out = eng.generate(list(range(1, 9)), max_new_tokens=4)
            assert len(out) == 4
        finally:
            eng.close()


class TestPagedAttentionKernel:
    """The Pallas paged-decode kernel vs the dense-gather reference —
    interpret mode runs the real kernel logic on CPU."""

    # (hq, hkv, table slots, contexts). At 8-token blocks of head_dim 16 a
    # page group of the kernel holds 64 pages, 512 tokens, so here the whole
    # table: the lanes' groups follow one another in the one stream of
    # copies (GQA_BANDS below walks several groups a lane).
    SHAPES = {
        "one-group": (4, 2, 6, [13, 0, 37]),
        # none, one row, mid-page, a part boundary, the whole table
        "edges": (4, 2, 20, [0, 1, 13, 128, 160]),
        "group7": (28, 4, 20, [5, 129, 77]),
        "mqa": (4, 1, 20, [160, 0, 64]),
    }

    LAYERS = 3  # the kernel takes the WHOLE stack and a layer's index

    @staticmethod
    def _inputs(seed, hq, hkv, MB, lengths, *, NB=40, d=16, Bk=8, chunk=4):
        """q, the pools as a token keeps them [L, NB, Bk, hkv, d] (every
        layer's rows its own), tables, the chunk's buffers, lengths."""
        rng = np.random.RandomState(seed)
        b, L = len(lengths), TestPagedAttentionKernel.LAYERS
        f = lambda *shape: jnp.asarray(rng.randn(*shape).astype(np.float32))  # noqa: E731
        q, pk, pv = f(b, 1, hq, d), f(L, NB, Bk, hkv, d), f(L, NB, Bk, hkv, d)
        tables = jnp.asarray(rng.randint(0, NB, size=(b, MB)).astype(np.int32))
        kb, vb = f(b, chunk, hkv, d), f(b, chunk, hkv, d)
        return q, pk, pv, tables, kb, vb, jnp.asarray(lengths, jnp.int32)

    @staticmethod
    def _slab(layer, *arrays):
        """that layer's slab of each [L, ...] array, as a stack of ONE layer:
        what the call took before it took the stack"""
        return tuple(a[layer:layer + 1] for a in arrays)

    # 9 ends inside a page; 40 spans pages and, from 128 on, two groups
    @pytest.mark.parametrize("layer", range(LAYERS))
    @pytest.mark.parametrize("window", [0, 9, 40])
    @pytest.mark.parametrize("shape", sorted(SHAPES))
    def test_kernel_matches_reference(self, shape, window, layer):
        """The kernel over layer `layer` of the whole stored stack == the
        gather reference over that layer's slab alone."""
        from gofr_tpu.ops.attention import paged_chunk_decode_attention

        q, pk, pv, tables, kb, vb, lengths = self._inputs(0, *self.SHAPES[shape])
        pk, pv = stored_rows(pk), stored_rows(pv)
        step = jnp.asarray(2, jnp.int32)
        ref = paged_chunk_decode_attention(
            q, *self._slab(layer, pk, pv), tables, kb, vb, lengths, step,
            layer=0, window=window, use_kernel=False,
        )
        kern = paged_chunk_decode_attention(
            q, pk, pv, tables, kb, vb, lengths, step,
            layer=jnp.asarray(layer, jnp.int32),
            window=window, use_kernel=True, interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(kern), np.asarray(ref), atol=2e-6
        )

    @pytest.mark.parametrize("layer", range(LAYERS))
    @pytest.mark.parametrize("window", [0, 40])
    @pytest.mark.parametrize("shape", ["one-group", "edges", "group7"])
    def test_kernel_int8(self, shape, window, layer):
        from gofr_tpu.ops.attention import paged_chunk_decode_attention

        q, pk, pv, tables, kb, vb, lengths = self._inputs(1, *self.SHAPES[shape])
        qk, sk = quantize_rows(pk)
        qv, sv = quantize_rows(pv)
        qk, qv = stored_rows(qk), stored_rows(qv)
        step = jnp.asarray(1, jnp.int32)
        s_k, s_v = self._slab(layer, sk, sv)
        ref = paged_chunk_decode_attention(
            q, *self._slab(layer, qk, qv), tables, kb, vb, lengths, step,
            layer=0, window=window, k_scales=s_k, v_scales=s_v, use_kernel=False,
        )
        kern = paged_chunk_decode_attention(
            q, qk, qv, tables, kb, vb, lengths, step, layer=layer, window=window,
            k_scales=sk, v_scales=sv, use_kernel=True, interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(kern), np.asarray(ref), atol=2e-6
        )

    @pytest.mark.parametrize("layer", range(LAYERS))
    @pytest.mark.parametrize("pool", ["f32", "int8"])
    def test_whole_stack_partials_equal_the_slabs(self, pool, layer):
        """_paged_decode_partials over layer `layer` of the stack == over
        that layer's slab given alone (o, m, l to the bit: the same pages
        reach the same arithmetic), with a band that starts past 0, table
        entries past the band that name no block, and an empty band."""
        from gofr_tpu.ops.attention import _paged_decode_partials

        hq, hkv, MB, _ = self.SHAPES["edges"]
        q, pk, pv, tables, _, _, _ = self._inputs(5, hq, hkv, MB, [0] * 4)
        lo = jnp.asarray([0, 21, 7, 50], jnp.int32)
        hi = jnp.asarray([13, 150, 7, 129], jnp.int32)  # lane 2: empty
        live = (np.arange(MB)[None, :] + 1) * 8 > np.asarray(lo)[:, None]
        live &= np.arange(MB)[None, :] * 8 < np.asarray(hi)[:, None]
        tables = jnp.where(jnp.asarray(live), tables, 40 + 1000)  # stale: outside the pool
        scales, slab_scales = {}, {}
        if pool == "int8":
            (pk, sk), (pv, sv) = quantize_rows(pk), quantize_rows(pv)
            scales = dict(k_scales=sk, v_scales=sv)
            slab_scales = dict(zip(("k_scales", "v_scales"), self._slab(layer, sk, sv)))
        pk, pv = stored_rows(pk), stored_rows(pv)
        kw = dict(scale=0.25, interpret=True)
        whole = _paged_decode_partials(q[:, 0], pk, pv, tables, lo, hi, layer, **kw, **scales)
        alone = _paged_decode_partials(
            q[:, 0], *self._slab(layer, pk, pv), tables, lo, hi, 0, **kw, **slab_scales
        )
        for w, a in zip(whole, alone):
            np.testing.assert_array_equal(np.asarray(w), np.asarray(a))
        o, m, l = (np.asarray(x) for x in whole)
        assert (l[2] == 0).all() and (o[2] == 0).all()  # the empty band
        assert (l[[0, 1, 3]] > 0).all() and np.isfinite(o).all()
        other = _paged_decode_partials(
            q[:, 0], pk, pv, tables, lo, hi, (layer + 1) % self.LAYERS, **kw, **scales
        )
        assert not np.allclose(np.asarray(other[0])[0], o[0])  # another layer's rows

    @pytest.mark.parametrize("layer", range(LAYERS))
    @pytest.mark.parametrize("window", [0, 40])
    @pytest.mark.parametrize("pool", ["f32", "int8"])
    def test_dead_pages_are_never_read(self, pool, window, layer):
        """Every pool block that no live table entry names holds NaN (an
        int8 pool: NaN scales), in EVERY layer but the one asked for whole,
        and every table entry outside a lane's band an id outside the pool:
        the kernel's output is finite and equals the reference's over the
        clean pool and table."""
        from gofr_tpu.ops.attention import paged_chunk_decode_attention

        hq, hkv, MB, lengths = self.SHAPES["edges"]
        Bk, NB = 8, 128
        q, pk, pv, _, kb, vb, lens = self._inputs(2, hq, hkv, MB, lengths, NB=NB)
        step = jnp.asarray(1, jnp.int32)
        # each lane's live pages are its own, scattered over the pool
        rng = np.random.RandomState(3)
        tables = rng.permutation(NB)[: len(lengths) * MB].reshape(-1, MB).astype(np.int32)
        slot = np.arange(MB)[None, :]
        hi = np.asarray(lengths)[:, None]
        lo = np.maximum(hi + 1 - window + 1, 0) if window else np.zeros_like(hi)
        live = (slot * Bk < hi) & ((slot + 1) * Bk > lo)
        dead_blocks = np.setdiff1d(np.arange(NB), tables[live])
        poisoned_tables = np.where(live, tables, rng.choice([-7, NB, NB + 1000], tables.shape))
        def poison(a):
            """NaN in the layer's dead blocks and all of every other layer"""
            return jnp.full_like(a, jnp.nan).at[layer].set(a[layer].at[dead_blocks].set(jnp.nan))

        if pool == "int8":
            (pk, sk), (pv, sv) = quantize_rows(pk), quantize_rows(pv)
            clean = dict(k_scales=sk, v_scales=sv)
            dirty = dict(k_scales=poison(sk), v_scales=poison(sv))
            pk, pv = stored_rows(pk), stored_rows(pv)
            dirty_k, dirty_v = pk, pv
        else:
            clean = dirty = {}
            pk, pv = stored_rows(pk), stored_rows(pv)
            dirty_k, dirty_v = poison(pk), poison(pv)
        ref = paged_chunk_decode_attention(
            q, pk, pv, jnp.asarray(tables), kb, vb, lens, step, layer=layer,
            window=window, use_kernel=False, **clean,
        )
        kern = paged_chunk_decode_attention(
            q, dirty_k, dirty_v, jnp.asarray(poisoned_tables), kb, vb, lens, step,
            layer=layer, window=window, use_kernel=True, interpret=True, **dirty,
        )
        assert np.isfinite(np.asarray(kern)).all()
        np.testing.assert_allclose(
            np.asarray(kern), np.asarray(ref), atol=2e-6
        )

    # (block, table slots): a page group holds 512 tokens of 4 bf16 heads of
    # 128 or of int8 heads, 256 of 8 bf16 heads (paged_decode_schedule), so
    # 32 / 16, 8 / 4 and 4 / 2 pages. 112 is the width max_seq_len 1,792
    # gave a 16-token table before a slot held the engine's merge slack,
    # 113 what a request can fill since, 120 the width it is built with;
    # the widths at 64 and 128 are the same rows. 113, 29 and 15 leave the
    # last page group partial.
    @pytest.mark.parametrize("window", [0, 300])
    @pytest.mark.parametrize("pool", ["bf16", "int8"])
    @pytest.mark.parametrize("hkv", [4, 8])
    @pytest.mark.parametrize(
        "block,n_tbl",
        [(16, 112), (16, 113), (16, 120), (64, 28), (64, 29), (64, 30), (128, 14), (128, 15)],
    )
    def test_kernel_at_serving_blocks(self, block, n_tbl, hkv, pool, window):
        """The kernel against paged_gather + chunk_decode_attention at the
        block sizes, head counts and table widths engines serve with:
        contexts of none, one row, mid-page, mid-table across page groups,
        one short of the table and the whole table."""
        from gofr_tpu.ops.attention import paged_chunk_decode_attention, paged_decode_schedule

        rows = n_tbl * block
        lengths = [0, 1, block + block // 2 + 1, rows // 2 + 3, rows - 1, rows]
        q, pk, pv, tables, kb, vb, lens = self._inputs(
            4, 2 * hkv, hkv, n_tbl, lengths, NB=24, d=128, Bk=block,
        )
        scales = {}
        if pool == "int8":
            (pk, sk), (pv, sv) = quantize_rows(pk), quantize_rows(pv)
            scales = dict(k_scales=sk, v_scales=sv)
        else:
            pk, pv = pk.astype(jnp.bfloat16), pv.astype(jnp.bfloat16)
        pk, pv = stored_rows(pk), stored_rows(pv)
        layer = n_tbl % self.LAYERS
        assert paged_decode_schedule(block, hkv, 128, pk.dtype, n_tbl).tokens == (
            256 if pool == "bf16" and hkv == 8 else 512
        )
        step = jnp.asarray(3, jnp.int32)
        # the reference reads the bf16 pool's own values widened to f32 (it
        # would otherwise round its probabilities to the pool's dtype)
        wide = (lambda a: a) if pool == "int8" else (lambda a: a.astype(jnp.float32))
        ref = paged_chunk_decode_attention(
            q, wide(pk), wide(pv), tables, kb, vb, lens, step, layer=layer,
            window=window, use_kernel=False, **scales,
        )
        kern = paged_chunk_decode_attention(
            q, pk, pv, tables, kb, vb, lens, step, layer=layer, window=window,
            use_kernel=True, interpret=True, **scales,
        )
        np.testing.assert_allclose(np.asarray(kern), np.asarray(ref), atol=5e-6)

    def test_tile_follows_the_pool_shape(self):
        """paged_decode_schedule: a group's pages and the groups in flight
        come from block size, local kv heads, head_dim and the pool's dtype
        alone: about 1 MB of K and V a group (128 to 512 tokens), 4 MB of
        buffers beside the one read (2 to 8 groups in flight)."""
        from gofr_tpu.ops.attention import paged_decode_schedule as sched

        bf16, i8 = jnp.bfloat16, jnp.int8
        assert sched(16, 4, 128, bf16, 112) == (32, 512, 3)  # qwen2: 1 MB a group
        assert sched(16, 4, 128, i8, 112) == (32, 512, 7)  # half the bytes, 512 tokens at most
        assert sched(64, 4, 128, bf16, 28) == (8, 512, 3)
        assert sched(128, 4, 128, bf16, 14) == (4, 512, 3)
        assert sched(16, 1, 128, bf16, 112) == (32, 512, 8)  # MQA: 8 in flight at most
        assert sched(16, 8, 128, bf16, 520) == (16, 256, 3)  # k-exaone
        assert sched(16, 30, 128, bf16, 129) == (8, 128, 2)  # olmo: 128 tokens, 2 in flight
        assert sched(16, 8, 256, bf16, 64) == (8, 128, 3)  # never under 128 tokens
        assert sched(16, 4, 128, bf16, 5) == (5, 80, 8)  # nor over the table
        # under a TP mesh the kernel runs per head shard: the LOCAL kv heads
        from gofr_tpu.parallel import make_mesh

        tp4 = make_mesh({"data": 1, "model": 4}, devices=jax.devices()[:4])
        assert sched(16, 4, 128, bf16, 120, hq=28, mesh=tp4) == (32, 512, 8)  # 1 head a shard
        assert sched(16, 8, 128, bf16, 120, hq=32, mesh=tp4) == (32, 512, 7)  # 2 heads a shard
        assert sched(16, 2, 128, bf16, 120, hq=32, mesh=tp4) == (32, 512, 7)  # replicated: both

    @pytest.mark.parametrize("int8,tile", [
        (False, {"pages": 16, "tokens": 256, "in_flight": 7, "walk": "across lanes"}),
        (True, {"pages": 16, "tokens": 256, "in_flight": 8, "walk": "across lanes"}),
    ])
    def test_engine_reports_the_tile(self, monkeypatch, int8, tile):
        """stats()["attention"] names the kernel's schedule beside `decode:
        pallas_paged` (the string the benchmark compares), from the
        engine's own pool: here 16-token blocks of 2 f32 / int8 heads of
        128 (a group held to the table's 16 slots). The CPU has no such
        kernel, so the REPORT is asked as a TPU would answer; nothing is
        traced."""
        cfg = TransformerConfig(
            vocab_size=64, d_model=256, n_layers=1, n_heads=2, n_kv_heads=2,
            head_dim=128, d_ff=64, dtype=jnp.float32,
        )
        eng = LLMEngine(
            cfg, init_params(jax.random.PRNGKey(0), cfg), slots=1, max_seq_len=240,
            warmup=False, kv_paged=True, kv_block=16, kv_int8=int8,
        )
        try:
            assert eng.stats()["attention"]["decode"].startswith("xla_gather")
            assert "decode_tile" not in eng.stats()["attention"]
            assert "pool_operand" not in eng.stats()["attention"]
            monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
            paths = eng._attention_paths()
            assert paths["decode"] == "pallas_paged"
            assert eng.kv.table_width == 16 and paths["decode_tile"] == tile
            # ...and what the kernel's pool operand is: the engine's own array
            assert paths["pool_operand"] == "whole stack"
            assert eng.cache.k.shape == eng.kv.pool_shapes()[0] == (1, eng.kv.pool.n_blocks, 16, 256)
        finally:
            eng.close()

    def test_paged_decode_chunk_matches_gather_path(self, params):
        """transformer.decode_chunk_paged (per-layer paged attention,
        interpret-mode kernel) == decode_chunk on the gathered view."""
        from gofr_tpu.kvcache.paged import gather_slots
        from gofr_tpu.models.transformer import (
            KVCache,
            decode_chunk,
            decode_chunk_paged,
            prefill,
        )

        rng = np.random.default_rng(4)
        prompt = rng.integers(1, CFG.vocab_size, 12).tolist()
        toks = jnp.asarray([prompt], jnp.int32)
        lens = jnp.asarray([12], jnp.int32)
        _, dense = prefill(params, CFG, toks, lens, 32)
        # lay the dense rows out as pool blocks 3,1,5,0 (scrambled)
        Bk = 8
        order = [3, 1, 5, 0]
        rows = ((CFG.n_kv_heads, CFG.head_dim),) * 2
        pool_k = jnp.zeros((CFG.n_layers, 8, Bk, CFG.n_kv_heads * CFG.head_dim))
        pool_v = jnp.zeros_like(pool_k)
        for j, blk in enumerate(order):
            pool_k = pool_k.at[:, blk].set(stored_rows(dense.k[:, 0, j * Bk : (j + 1) * Bk]))
            pool_v = pool_v.at[:, blk].set(stored_rows(dense.v[:, 0, j * Bk : (j + 1) * Bk]))
        tables = jnp.asarray([order], jnp.int32)
        pool = KVCache(k=pool_k, v=pool_v, length=dense.length)
        active = jnp.asarray([True])
        temps = jnp.zeros((1,), jnp.float32)
        sample = lambda lg, t, k: jnp.argmax(lg, axis=-1).astype(jnp.int32)  # noqa: E731
        t0 = jnp.asarray([prompt[-1]], jnp.int32)
        rng0 = jax.random.PRNGKey(0)
        toks_p, last_p, pool2, _, _ = decode_chunk_paged(
            params, CFG, t0, pool, None, tables, active, temps, rng0,
            n_steps=4, sample_fn=sample, block=Bk,
            use_kernel=True, interpret=True,
        )
        view = gather_slots(pool.k, pool.v, tables, pool.length, rows=rows)
        toks_d, last_d, _, _ = decode_chunk(
            params, CFG, t0, view, active, temps, rng0,
            n_steps=4, sample_fn=sample,
        )
        np.testing.assert_array_equal(np.asarray(toks_p), np.asarray(toks_d))
        # merged rows land in the right blocks (positions 12..15 -> block
        # order[1], rows 4..7)
        view2 = gather_slots(pool2.k, pool2.v, tables, pool2.length, rows=rows)
        np.testing.assert_allclose(
            np.asarray(view2.k[:, 0, 12:16]),
            np.asarray(
                decode_chunk(
                    params, CFG, t0, view, active, temps, rng0,
                    n_steps=4, sample_fn=sample,
                )[2].k[:, 0, 12:16]
            ),
            atol=2e-6,
        )


# -- the pool reaches its decode kernel as it is stored ----------------------


def _walk(jaxpr, origin, inside_steps, found):
    """Every equation of `jaxpr` and of the programs nested in it. `origin`
    maps a variable to the top-level input it IS (passed down unchanged: a
    scan's constants, a jit's arguments); `found` collects the pallas calls
    (their operands' origins) and, inside the scan over decode steps, the
    largest result any equation yields."""
    from jax.extend.core import ClosedJaxpr, Jaxpr, Literal

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "pallas_call":
            found["calls"].append([
                (tuple(v.aval.shape), None if isinstance(v, Literal) else origin.get(v))
                for v in eqn.invars
            ])
            continue
        if inside_steps:
            for v in eqn.outvars:
                size = int(np.prod(getattr(v.aval, "shape", ())))
                if size > found["largest"][0]:
                    found["largest"] = (size, name, tuple(v.aval.shape))
        subs = [
            j.jaxpr if isinstance(j, ClosedJaxpr) else j
            for p in eqn.params.values() for j in (p if isinstance(p, (tuple, list)) else (p,))
            if isinstance(j, (ClosedJaxpr, Jaxpr))
        ]
        straight = {"scan": eqn.params.get("num_consts", 0), "jit": len(eqn.invars),
                    "pjit": len(eqn.invars)}.get(name, 0)
        for sub in subs:
            inner = {
                iv: origin[ov] for iv, ov in zip(sub.invars[:straight], eqn.invars[:straight])
                if not isinstance(ov, Literal) and ov in origin
            }
            _walk(sub, inner, inside_steps or name == "scan", found)


@pytest.mark.parametrize("preset", ["tiny_qwen2", "tiny_latent_moe"])
def test_the_pool_reaches_its_decode_kernel_as_it_is_stored(preset):
    """decode_chunk_paged on the kernel path, traced: each pallas call's pool
    operands have the WHOLE pool's shape and are the program's own inputs
    (nothing computed them: no slice, no view), and no equation inside the
    scan over decode steps, the layer scan's body with it, yields an array
    as large as one layer's pool (NB * B * W elements)."""
    from gofr_tpu.models.transformer import KVCache, decode_chunk_paged

    cfg = getattr(TransformerConfig, preset)()
    params = init_params(jax.random.PRNGKey(0), cfg)
    kv = CacheManager(cfg, 2, 96, 4, paged=True, block=16, pool_blocks=256)
    pool, _ = kv.pool_arrays(jnp)
    k_shape, v_shape = kv.pool_shapes()
    b, K = 2, 4
    args = (
        params, jnp.zeros((b,), jnp.int32), pool, jnp.zeros((b, kv.table_width), jnp.int32),
        jnp.ones((b,), bool), jnp.zeros((b,)), jax.random.PRNGKey(1),
    )
    jaxpr = jax.make_jaxpr(
        lambda p, tok, pool, tables, act, temps, rng: decode_chunk_paged(
            p, cfg, tok, pool, None, tables, act, temps, rng, n_steps=K, block=16,
            sample_fn=lambda lg, t, k: jnp.argmax(lg, -1), use_kernel=True, interpret=True,
        )
    )(*args).jaxpr
    n_params = len(jax.tree.leaves(params))
    pool_in = {jaxpr.invars[n_params + 1]: "pool.k", jaxpr.invars[n_params + 2]: "pool.v"}
    assert [tuple(v.aval.shape) for v in pool_in] == [k_shape, v_shape]
    found = {"calls": [], "largest": (0, None, None)}
    _walk(jaxpr, dict(pool_in), False, found)
    assert found["calls"], "no pallas call was traced"
    for operands in found["calls"]:
        pools = [(shape, src) for shape, src in operands if len(shape) == 4 and shape[:3] == k_shape[:3]]
        assert pools == [(k_shape, "pool.k"), (v_shape, "pool.v")], operands
    layer_pool = min(int(np.prod(s[1:])) for s in (k_shape, v_shape))
    assert 0 < found["largest"][0] < layer_pool, found["largest"]


# -- the GQA kernel's walk over several page groups a lane ------------------------------


# Each lane's band [lo, hi) and the table's slots. The test's f32 rows of 2
# kv heads of 128 make a page group 512 tokens (64 pages of 8), read at the
# band's edge in parts of 256 rows, and keep 3 groups in flight
# (paged_decode_schedule); the kernel walks every lane's groups as ONE
# stream of copies, so what a band looks like next to its neighbours
# matters.
GQA_BANDS = {
    "the first and last lanes empty, an empty lane between live ones": ([0] * 6, [0, 600, 0, 280, 1200, 0], 160),
    "every lane empty": ([0] * 3, [0] * 3, 160),
    "one lane alone": ([0], [400], 160),
    "bands that start mid-group and end mid-page (a window)": ([5, 520, 0, 800], [400, 1200, 160, 1268], 160),
    "a lane inside one whole group, an edge group at both ends": ([512, 300, 0], [1024, 1200, 512], 160),
    "a table that leaves the last group partial": ([0, 0, 12], [1184, 68, 1160], 148),
    "more groups than buffers in flight": ([0] * 5, [1280, 1000, 0, 1240, 520], 160),
}
GQA_WALK = dict(hq=4, hkv=2, d=128, block=8)


def _gqa_walk_inputs(b, MB, pool, seed=0):
    """q [b, hq, d] and the pools as stored, a stack of two layers
    [2, NB, 8, hkv * d] read at the second (int8: with its scales),
    tables over the pool in random order."""
    hq, hkv, d, Bk = (GQA_WALK[k] for k in ("hq", "hkv", "d", "block"))
    rng = np.random.default_rng(seed)
    NB = b * MB
    q = jnp.asarray(rng.standard_normal((b, hq, d)).astype(np.float32))
    pk, pv = (jnp.asarray(rng.standard_normal((2, NB, Bk, hkv, d)).astype(np.float32)) for _ in range(2))
    scales = {}
    if pool == "int8":
        (pk, sk), (pv, sv) = quantize_rows(pk), quantize_rows(pv)
        scales = dict(k_scales=sk, v_scales=sv)
    tables = rng.permutation(NB).reshape(b, MB).astype(np.int32)
    return q, stored_rows(pk), stored_rows(pv), tables, scales


def _gqa_band_partials(q, pk, pv, tables, lo, hi, layer, scale, scales):
    """The kernel's partials over [lo, hi) from the gathered rows, by XLA:
    (o normalized, m, l); an empty band gives l = 0 and no o or m."""
    from gofr_tpu.ops.attention import paged_gather

    b, hq, d = q.shape
    hkv = GQA_WALK["hkv"]
    kc, vc = paged_gather(pk, pv, jnp.asarray(tables), layer, ((hkv, d), (hkv, d)), dtype=jnp.float32, **scales)
    s = jnp.einsum("bhgd,bnhd->bhgn", (q * scale).reshape(b, hkv, hq // hkv, d), kc, precision="highest")
    pos = jnp.arange(kc.shape[1])
    live = ((pos >= jnp.asarray(lo)[:, None]) & (pos < jnp.asarray(hi)[:, None]))[:, None, None]
    s = jnp.where(live, s, -jnp.inf)
    m = jnp.max(s, axis=-1)
    p = jnp.where(live, jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhgn,bnhd->bhgd", p, vc, precision="highest") / jnp.where(l == 0, 1.0, l)[..., None]
    return o.reshape(b, hq, d), m.reshape(b, hq), l.reshape(b, hq)


def _gqa_simulated_partials(q, pk, pv, tables, lo, hi, layer, scale, scales, seconds=300):
    """The kernel's partials in the TPU interpreter that simulates its DMAs
    and semaphores (a copy lands when it is waited for; a read outside a
    buffer raises; VMEM starts as NaN), on a thread of its own: the
    simulator blocks for ever on a wait for a copy never started, which
    fails here instead."""
    import threading

    from jax.experimental.pallas import tpu as pltpu

    from gofr_tpu.ops.attention import _paged_decode_partials

    out = []

    def run():
        try:
            out.append(jax.block_until_ready(_paged_decode_partials(
                q, pk, pv, jnp.asarray(tables), jnp.asarray(lo, jnp.int32), jnp.asarray(hi, jnp.int32),
                layer, scale=scale, interpret=pltpu.InterpretParams(), **scales,
            )))
        except Exception as e:  # noqa: BLE001 — raised again on the test's thread
            out.append(e)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(seconds)
    assert out, f"the kernel's copy stream did not finish in {seconds} s: a wait without its copy"
    if isinstance(out[0], Exception):
        raise out[0]
    return out[0]


def _assert_gqa_partials_equal(got, want, lo, hi):
    empty = np.asarray(hi) <= np.asarray(lo)
    o, m, l = (np.asarray(x) for x in got)
    wo, wm, wl = (np.asarray(x) for x in want)
    assert (l[empty] == 0).all() and (o[empty] == 0).all()
    live = ~empty
    np.testing.assert_allclose(o[live], wo[live], atol=2e-5)
    np.testing.assert_allclose(m[live], wm[live], rtol=1e-6, atol=1e-5)
    np.testing.assert_allclose(l[live], wl[live], rtol=1e-5)


@pytest.mark.parametrize("pool", ["f32", "int8"])
@pytest.mark.parametrize("case", list(GQA_BANDS))
def test_the_gqa_walk_across_lanes_equals_the_gathered_band(case, pool):
    """The GQA kernel against the gathered rows, band by band, over several
    page groups a lane, with its copies simulated: whole groups read
    without a mask, edge groups in parts, empty lanes passed over."""
    from gofr_tpu.ops.attention import paged_decode_schedule

    lo, hi, MB = GQA_BANDS[case]
    q, pk, pv, tables, scales = _gqa_walk_inputs(len(hi), MB, pool)
    sched = paged_decode_schedule(8, 2, 128, pk.dtype, MB)
    assert (sched.tokens, sched.in_flight) == (512, 3 if pool == "f32" else 8)
    groups = sum(-(-h // sched.tokens) - l_ // sched.tokens for l_, h in zip(lo, hi) if h > l_)
    assert case != "more groups than buffers in flight" or groups > 3 + 1
    got = _gqa_simulated_partials(q, pk, pv, tables, lo, hi, 1, 0.1, scales)
    _assert_gqa_partials_equal(got, _gqa_band_partials(q, pk, pv, tables, lo, hi, 1, 0.1, scales), lo, hi)


@pytest.mark.parametrize("pool", ["f32", "int8"])
def test_the_gqa_walk_never_reads_a_dead_page(pool):
    """NaN in every pool block outside the lanes' bands and in the whole of
    the other layer (an int8 pool: its scales), and every table entry
    outside a band (before lo and past hi) an id outside the pool, over
    bands that start mid-group and end mid-page: the partials are finite
    and equal those over the clean pool and table."""
    lo, hi, MB = GQA_BANDS["bands that start mid-group and end mid-page (a window)"]
    q, pk, pv, tables, scales = _gqa_walk_inputs(len(hi), MB, pool, seed=4)
    Bk = GQA_WALK["block"]
    slot = np.arange(MB)[None, :]
    live = (slot * Bk < np.asarray(hi)[:, None]) & ((slot + 1) * Bk > np.asarray(lo)[:, None])
    NB = pk.shape[1]
    dead = np.setdiff1d(np.arange(NB), tables[live])
    poisoned = np.where(live, tables, np.random.default_rng(0).choice([-7, NB, NB + 1000], tables.shape))

    def poison(a):
        return jnp.full_like(a, jnp.nan).at[1].set(a[1].at[dead].set(jnp.nan))

    if pool == "int8":
        dirty = (pk, pv, {k: poison(v) for k, v in scales.items()})
    else:
        dirty = (poison(pk), poison(pv), scales)
    got = _gqa_simulated_partials(q, dirty[0], dirty[1], poisoned, lo, hi, 1, 0.1, dirty[2])
    assert all(np.isfinite(np.asarray(x)).all() for x in got)
    _assert_gqa_partials_equal(got, _gqa_band_partials(q, pk, pv, tables, lo, hi, 1, 0.1, scales), lo, hi)


@pytest.mark.parametrize("pool", ["f32", "int8"])
def test_the_gqa_walk_over_the_stack_equals_it_over_the_slab(pool):
    """_paged_decode_partials over the second layer of the stack == over
    that layer's slab given alone, to the bit, over several page groups a
    lane: the same pages reach the same arithmetic in the same order."""
    from gofr_tpu.ops.attention import _paged_decode_partials

    lo, hi, MB = GQA_BANDS["a lane inside one whole group, an edge group at both ends"]
    q, pk, pv, tables, scales = _gqa_walk_inputs(len(hi), MB, pool, seed=2)
    slab = {k: v[1:] for k, v in scales.items()}
    args = (jnp.asarray(tables), jnp.asarray(lo, jnp.int32), jnp.asarray(hi, jnp.int32))
    whole = _paged_decode_partials(q, pk, pv, *args, 1, scale=0.1, interpret=True, **scales)
    alone = _paged_decode_partials(q, pk[1:], pv[1:], *args, 0, scale=0.1, interpret=True, **slab)
    for w, a in zip(whole, alone):
        np.testing.assert_array_equal(np.asarray(w), np.asarray(a))

"""Parallelism tests on the virtual 8-device CPU mesh (conftest.py) — the
same code path the driver's dryrun_multichip exercises."""

import threading

import jax
import jax.numpy as jnp
import pytest

from gofr_tpu.models import (
    MLPConfig,
    TransformerConfig,
    init_params,
    mlp_forward,
    mlp_init,
    prefill,
)
from gofr_tpu.ops import mha_reference
from gofr_tpu.parallel import (
    lm_loss,
    make_mesh,
    make_train_step,
    mesh_shape_for,
    mlp_param_specs,
    param_specs,
    place_batch,
    ring_attention,
    shard_params,
)

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices"
)


class TestMesh:
    def test_default_factorization_prefers_tp(self):
        assert mesh_shape_for(8) == {"data": 1, "model": 8}
        assert mesh_shape_for(8, tp=4) == {"data": 2, "model": 4}

    def test_mesh_shape_mismatch_raises(self):
        with pytest.raises(ValueError):
            make_mesh({"data": 3, "model": 5})


class TestRingAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_reference(self, causal):
        mesh = make_mesh({"seq": 8})
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(kk, (2, 64, 4, 32)) for kk in ks)
        ref = mha_reference(q, k, v, causal=causal)
        out = ring_attention(q, k, v, mesh=mesh, axis="seq", causal=causal)
        assert jnp.abs(ref - out).max() < 2e-5


class TestTensorParallel:
    @pytest.mark.parametrize("tp", [2, 4, 8])
    def test_tp_prefill_matches_single_device(self, tp):
        """The same params sharded over the model axis must produce the
        single-device logits — GSPMD collectives are numerically
        transparent. The long-standing tp=8 failure ("TP prefill drift",
        flagged since PR 2) was not reduction-order noise: tiny's
        4 heads x 16 head_dim sharded 8 ways put a shard boundary INSIDE
        each head, which an earlier jax/XLA miscompiled through the
        rope/attention reshapes (logits off by ~1.0, cache rows by ~3.5).
        param_specs now shards q/o at whole-head granularity only
        (replicated when tp does not divide n_heads, the kv rule), so
        every degree here is collective-exact."""
        cfg = TransformerConfig.tiny()
        params = init_params(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab_size)
        lens = jnp.array([8, 8], jnp.int32)
        ref_logits, _ = prefill(params, cfg, toks, lens, 16)

        mesh = make_mesh(
            {"data": 1, "model": tp}, devices=jax.devices()[:tp]
        )
        sharded = shard_params(params, mesh, param_specs(cfg, mesh))
        tp_logits, _ = jax.jit(lambda p, t, l: prefill(p, cfg, t, l, 16))(
            sharded, toks, lens
        )
        assert jnp.abs(ref_logits - tp_logits).max() < 1e-3

    def test_mlp_tp_matches_single_device(self):
        cfg = MLPConfig(in_dim=16, hidden=(32, 64), out_dim=8, dtype=jnp.float32)
        params = mlp_init(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16))
        ref = mlp_forward(params, x)
        mesh = make_mesh({"data": 1, "model": 8})
        sharded = shard_params(params, mesh, mlp_param_specs(params, mesh))
        out = jax.jit(mlp_forward)(sharded, x)
        assert jnp.abs(ref - out).max() < 1e-4

    def test_mqa_kv_replicated(self):
        P = jax.sharding.PartitionSpec
        cfg = TransformerConfig.tiny()  # n_kv_heads=2, tp=8 -> replicate kv
        mesh = make_mesh({"data": 1, "model": 8})
        specs = param_specs(cfg, mesh)
        assert specs["layers"]["wkv"] == P(None, None, None)
        # n_heads=4, tp=8: an 8-way shard would split inside each head —
        # replicated (whole-head granularity; see test_tp_prefill above)
        assert specs["layers"]["wq"] == P(None, None, None)
        # tp=4 divides n_heads=4: q/o shard, kv (2 heads) replicates
        mesh4 = make_mesh(
            {"data": 1, "model": 4}, devices=jax.devices()[:4]
        )
        specs4 = param_specs(cfg, mesh4)
        assert specs4["layers"]["wq"] == P(None, None, "model")
        assert specs4["layers"]["wo"] == P(None, "model", None)
        assert specs4["layers"]["wkv"] == P(None, None, None)
        # tp=2 divides both: everything shards
        mesh2 = make_mesh(
            {"data": 1, "model": 2}, devices=jax.devices()[:2]
        )
        specs2 = param_specs(cfg, mesh2)
        assert specs2["layers"]["wq"] == P(None, None, "model")
        assert specs2["layers"]["wkv"] == P(None, None, "model")


class TestTrainStep:
    def test_loss_decreases_dp_tp(self):
        cfg = TransformerConfig.tiny()
        mesh = make_mesh({"data": 2, "model": 4})
        params = init_params(jax.random.PRNGKey(0), cfg)
        shard_fn, init_opt, step = make_train_step(cfg, mesh, learning_rate=1e-2)
        params = shard_fn(params)
        opt_state = init_opt(params)
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size)
        mask = jnp.ones_like(toks, dtype=bool)
        toks, mask = place_batch((toks, mask), mesh)
        first = None
        for _ in range(5):
            params, opt_state, loss = step(params, opt_state, toks, mask)
            first = first if first is not None else float(loss)
        assert float(loss) < first

    def test_loss_masks_padding(self):
        cfg = TransformerConfig.tiny()
        params = init_params(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab_size)
        full = jnp.ones_like(toks, dtype=bool)
        half = full.at[:, 4:].set(False)
        # Changing masked-out tokens must not change the loss.
        toks2 = toks.at[:, 6].set((toks[:, 6] + 1) % cfg.vocab_size)
        l1 = lm_loss(params, cfg, toks, half)
        l2 = lm_loss(params, cfg, toks2, half)
        assert abs(float(l1) - float(l2)) < 1e-6


class TestDPServing:
    """SURVEY §2.8 row 1: replicated serving across chips with per-replica
    dispatch. Replicas are full engines pinned to distinct devices; the
    router must preserve per-request results exactly (continuous batching
    may change placement, never tokens)."""

    def _reference(self, params, cfg, prompt, n):
        from gofr_tpu.models import generate
        import numpy as np

        toks = jnp.asarray([prompt], jnp.int32)
        lens = jnp.asarray([len(prompt)], jnp.int32)
        return [int(t) for t in np.asarray(generate(params, cfg, toks, lens, n))[0]]

    @pytest.mark.slow  # ~20s: builds 2 full engines + a reference decode
    def test_dp_replicas_match_single_engine(self):
        from gofr_tpu.llm import ReplicatedLLMEngine

        cfg = TransformerConfig.tiny()
        params = init_params(jax.random.PRNGKey(0), cfg)
        eng = ReplicatedLLMEngine(
            cfg, params, replicas=2, slots=2, max_seq_len=64,
            prefill_buckets=(8,), router="least_loaded",
        )
        try:
            assert len(eng.engines) == 2
            # replicas sit on distinct devices
            devs = {
                next(iter(jax.tree.leaves(e.params)[0].devices()))
                for e in eng.engines
            }
            assert len(devs) == 2
            from gofr_tpu.llm import GenRequest

            # submit back-to-back (before any completes): least-loaded sees
            # each prior submission in load() and must alternate replicas
            prompts = [[5, 9, 2], [7, 1], [3, 3, 4], [11, 2, 6, 1]]
            reqs = [
                eng.submit(GenRequest(p, max_new_tokens=5)) for p in prompts
            ]
            outs = [r.tokens() for r in reqs]
            for p, got in zip(prompts, outs):
                assert got == self._reference(params, cfg, p, 5)
            # the router must actually have dispatched to BOTH replicas
            st = eng.stats()
            assert st["replicas"] == 2 and st["slots"] == 4
            assert all(s["submitted"] >= 1 for s in st["per_replica"]), st
        finally:
            eng.close()

    def test_round_robin_alternates(self):
        from gofr_tpu.llm import ReplicatedLLMEngine

        cfg = TransformerConfig.tiny()
        params = init_params(jax.random.PRNGKey(0), cfg)
        eng = ReplicatedLLMEngine(
            cfg, params, replicas=2, slots=2, max_seq_len=32,
            prefill_buckets=(8,), router="round_robin", warmup=False,
        )
        try:
            picks = [eng._pick() for _ in range(4)]
            assert picks[0] is not picks[1] and picks[0] is picks[2]
        finally:
            eng.close()

    def test_dp_over_tp_submeshes(self):
        """dp=2 x tp=4: each replica tensor-parallel over its own 4-device
        submesh — the full composition config 5 implies."""
        from gofr_tpu.llm import ReplicatedLLMEngine

        cfg = TransformerConfig.tiny()
        params = init_params(jax.random.PRNGKey(0), cfg)
        devs = jax.devices()
        meshes = []
        for half in (devs[:4], devs[4:]):
            mesh = jax.sharding.Mesh([half], ("data", "model"))
            meshes.append((mesh, param_specs(cfg, mesh)))
        eng = ReplicatedLLMEngine(
            cfg, params, meshes=meshes, slots=2, max_seq_len=64,
            prefill_buckets=(8,),
        )
        try:
            prompt = [5, 9, 2]
            got = eng.generate(prompt, max_new_tokens=5)
            assert got == self._reference(params, cfg, prompt, 5)
            # both replicas alive and on disjoint device sets
            d0 = set(jax.tree.leaves(eng.engines[0].params)[0].devices())
            d1 = set(jax.tree.leaves(eng.engines[1].params)[0].devices())
            assert d0.isdisjoint(d1) and len(d0) == 4 and len(d1) == 4
        finally:
            eng.close()

    def test_replica_death_fails_over_queue_and_reroutes(self):
        """When one replica's scheduler thread dies (an escape past the
        per-iteration recovery handler), its queued requests must be
        FAILED OVER to the survivor — completed, not errored (PR-5
        resilience; previously they were end-of-streamed as "cancelled")
        — and the router must stop feeding the dead replica
        (VERDICT r4 #7). supervise=False isolates routing semantics from
        the restart path (tests/test_resilience.py covers restarts)."""
        import time as _time

        from gofr_tpu.llm import GenRequest, ReplicatedLLMEngine

        cfg = TransformerConfig.tiny()
        params = init_params(jax.random.PRNGKey(0), cfg)
        eng = ReplicatedLLMEngine(
            cfg, params, replicas=2, slots=2, max_seq_len=64,
            prefill_buckets=(8,), router="round_robin", warmup=False,
            supervise=False,
        )
        try:
            victim, survivor = eng.engines
            # wedge the victim's scheduler in a patched _admit, then make
            # it raise a BaseException that escapes `except Exception`
            entered, release = threading.Event(), threading.Event()

            def dying_admit():
                entered.set()
                release.wait(timeout=10)
                raise SystemExit  # daemon-thread-silent, escapes recovery

            victim._admit = dying_admit
            # wait until the scheduler is INSIDE the patch (its in-progress
            # real _admit call could otherwise still consume the queue)
            assert entered.wait(timeout=10)
            # park a request in the victim's admit queue while its
            # scheduler is wedged
            parked = victim.submit(GenRequest([5, 9, 2], max_new_tokens=5))
            release.set()
            victim._thread.join(timeout=10)
            assert not victim._thread.is_alive()
            # death is detected promptly
            deadline = _time.time() + 10
            while victim.alive() and _time.time() < deadline:
                _time.sleep(0.01)
            assert not victim.alive()
            # the parked request rides the failover hook onto the
            # survivor and COMPLETES, token-identical to an unfaulted run
            toks = parked.tokens()
            assert parked.finish_reason == "length"
            assert toks == self._reference(params, cfg, [5, 9, 2], 5)
            assert eng.failovers == 1
            # router only feeds the survivor now — round-robin over 1
            for _ in range(4):
                r = eng.submit(GenRequest([7, 1], max_new_tokens=3))
                assert r.tokens() == self._reference(params, cfg, [7, 1], 3)
            st = eng.stats()
            assert st["replicas"] == 2 and st["replicas_alive"] == 1
            assert all(eng._pick() is survivor for _ in range(4))
        finally:
            eng.close()

    def test_submit_racing_death_does_not_hang(self):
        """A submit that passes the _stop check just before _die's drain
        must still be ended (code-review TOCTOU finding): the post-put
        re-check drains the queue itself."""
        from gofr_tpu.llm import GenRequest, LLMEngine

        cfg = TransformerConfig.tiny()
        params = init_params(jax.random.PRNGKey(0), cfg)
        eng = LLMEngine(
            cfg, params, slots=2, max_seq_len=64, prefill_buckets=(8,),
            warmup=False,
        )
        try:
            # simulate the race deterministically: flip _stop between the
            # submit-side check and the put by patching the EMA update's
            # lock acquisition window — simplest faithful stand-in is to
            # run _die first but call the post-check path directly
            eng._die("injected for race test")
            req = GenRequest([5, 9, 2], max_new_tokens=4)
            req.submitted_at = 0.0
            eng._admit_q.put(req)  # what submit() does after its check
            if eng._stop:  # the re-check submit() now performs
                eng._drain_pending()
            assert req.finish_reason == "cancelled"
            assert req.tokens() == []
        finally:
            eng.close()

    def test_register_llm_replicated(self):
        from gofr_tpu.datasource.tpu import TPURuntime
        from gofr_tpu.llm import ReplicatedLLMEngine

        cfg = TransformerConfig.tiny()
        params = init_params(jax.random.PRNGKey(0), cfg)
        rt = TPURuntime()
        try:
            eng = rt.register_llm(
                "tiny", cfg, params, replicas=2, slots=2, max_seq_len=32,
                prefill_buckets=(8,), warmup=False,
            )
            # register_llm returns the versioned ModelHandle (rollouts);
            # the replicated engine sits behind it, full surface proxied
            assert isinstance(eng.engine, ReplicatedLLMEngine)
            assert rt.llm("tiny") is eng
            assert eng.version == "v1" and len(eng.engines) == 2
        finally:
            rt.close()


class TestPipelineParallel:
    """GPipe-style depth sharding (parallel/pipeline.py): the layer stack
    split over a `stage` mesh axis, microbatches streamed via ppermute.
    SURVEY.md §2.8's one stretch row."""

    def _setup(self, n_stages=4, n_layers=4, n_micro=4, b=8, s=16):
        import dataclasses

        import numpy as np

        from jax.sharding import Mesh

        from gofr_tpu.parallel import (
            make_pp_train_step,
            pipeline_layers,
            pp_lm_loss,
        )

        cfg = dataclasses.replace(TransformerConfig.tiny(), n_layers=n_layers)
        params = init_params(jax.random.PRNGKey(0), cfg)
        mesh = Mesh(
            np.array(jax.devices()[:n_stages]).reshape(n_stages), ("stage",)
        )
        rng = np.random.default_rng(0)
        tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32)
        mask = jnp.ones((b, s), bool)
        shard_fn, init_opt, step_fn = make_pp_train_step(
            cfg, mesh, n_micro=n_micro
        )
        pp_fn = pipeline_layers(cfg, mesh)
        return cfg, params, mesh, tokens, mask, shard_fn, init_opt, step_fn, pp_fn, pp_lm_loss

    def test_loss_matches_single_device(self):
        (cfg, params, mesh, tokens, mask,
         shard_fn, _io, _st, pp_fn, pp_loss) = self._setup()
        ref = lm_loss(params, cfg, tokens, mask)
        got = pp_loss(shard_fn(params), cfg, tokens, mask, pp_fn, 4)
        assert abs(float(ref) - float(got)) < 1e-5

    @pytest.mark.slow  # ~17s: compiles grad-of-pp-scan over 8 stages
    def test_grads_match_single_device(self):
        (cfg, params, mesh, tokens, mask,
         shard_fn, _io, _st, pp_fn, pp_loss) = self._setup()
        g_ref = jax.grad(lm_loss)(params, cfg, tokens, mask)
        g_pp = jax.grad(pp_loss)(shard_fn(params), cfg, tokens, mask, pp_fn, 4)
        err = max(
            jax.tree.leaves(
                jax.tree.map(
                    lambda a, b: float(jnp.max(jnp.abs(a - b))), g_ref, g_pp
                )
            )
        )
        assert err < 1e-5, f"max grad err {err}"

    def test_train_step_decreases_loss(self):
        (cfg, params, mesh, tokens, mask,
         shard_fn, init_opt, step_fn, _pp, _pl) = self._setup()
        p = shard_fn(params)
        o = init_opt(p)
        losses = []
        for _ in range(4):
            p, o, loss = step_fn(p, o, tokens, mask)
            losses.append(float(loss))
        assert losses[-1] < losses[0]

    def test_eight_stages(self):
        """One layer per stage across the whole 8-device mesh."""
        (cfg, params, mesh, tokens, mask,
         shard_fn, _io, _st, pp_fn, pp_loss) = self._setup(
            n_stages=8, n_layers=8, n_micro=2, b=4
        )
        ref = lm_loss(params, cfg, tokens, mask)
        got = pp_loss(shard_fn(params), cfg, tokens, mask, pp_fn, 2)
        assert abs(float(ref) - float(got)) < 1e-5

    def test_indivisible_layers_raise(self):
        import dataclasses

        import numpy as np

        from jax.sharding import Mesh

        from gofr_tpu.parallel import make_pp_train_step

        cfg = dataclasses.replace(TransformerConfig.tiny(), n_layers=3)
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("stage",))
        with pytest.raises(ValueError):
            make_pp_train_step(cfg, mesh, n_micro=2)


class TestUntiedSharding:
    def test_train_step_shards_untied_params(self):
        """An unembed leaf (untied Llama head) must shard without a pytree
        mismatch in both train-step factories (specs derive untied-ness
        from the params, not the config)."""
        import dataclasses

        import numpy as np

        from jax.sharding import Mesh

        from gofr_tpu.parallel import make_pp_train_step

        cfg = TransformerConfig.tiny()
        params = init_params(jax.random.PRNGKey(0), cfg)
        params = dict(
            params,
            unembed=jax.random.normal(
                jax.random.PRNGKey(1), (cfg.vocab_size, cfg.d_model), jnp.float32
            ),
        )
        mesh = make_mesh({"data": 2, "model": 4})
        shard_fn, _io, _st = make_train_step(cfg, mesh)
        sp = shard_fn(params)
        assert "unembed" in sp

        pcfg = dataclasses.replace(cfg, n_layers=4)
        pparams = init_params(jax.random.PRNGKey(0), pcfg)
        pparams = dict(pparams, unembed=params["unembed"])
        pmesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("stage",))
        pshard, _pi, _ps = make_pp_train_step(pcfg, pmesh, n_micro=2)
        psp = pshard(pparams)
        assert "unembed" in psp

    def test_llm_engine_tp_untied_params(self):
        """TP serving of an untied-head (Llama) checkpoint with the stock
        param_specs(cfg, mesh) — the engine patches in the unembed spec
        rather than crashing shard_params (review r4)."""
        from gofr_tpu.llm import LLMEngine

        cfg = TransformerConfig.tiny()
        params = init_params(jax.random.PRNGKey(0), cfg)
        params = dict(
            params,
            unembed=jax.random.normal(
                jax.random.PRNGKey(2), (cfg.vocab_size, cfg.d_model), jnp.float32
            )
            * 0.02,
        )
        mesh = make_mesh({"data": 1, "model": 8})
        eng = LLMEngine(
            cfg, params, slots=2, max_seq_len=32, prefill_buckets=(8,),
            decode_chunk=4, mesh=mesh, param_specs=param_specs(cfg, mesh),
        )
        try:
            got = eng.generate([5, 9, 2], max_new_tokens=4)
        finally:
            eng.close()
        eng1 = LLMEngine(
            cfg, params, slots=2, max_seq_len=32, prefill_buckets=(8,),
            decode_chunk=4,
        )
        try:
            want = eng1.generate([5, 9, 2], max_new_tokens=4)
        finally:
            eng1.close()
        assert got == want


class TestRingPrefill:
    """Sequence-parallel prefill (parallel/ring.ring_prefill): full
    transformer forward with seq-sharded activations + ring attention,
    vs the dense single-device prefill oracle."""

    def _setup(self, s=64):
        import numpy as np

        cfg = TransformerConfig.tiny()
        params = init_params(jax.random.PRNGKey(0), cfg)
        mesh = make_mesh({"seq": 8})
        rng = np.random.default_rng(0)
        toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (2, s)), jnp.int32)
        lens = jnp.asarray([s, s - 10], jnp.int32)
        return cfg, params, mesh, toks, lens

    def test_matches_dense_prefill(self):
        from gofr_tpu.parallel.ring import ring_prefill

        cfg, params, mesh, toks, lens = self._setup()
        ref_logits, ref_cache = prefill(params, cfg, toks, lens, toks.shape[1])
        got_logits, got_cache = ring_prefill(params, cfg, toks, lens, mesh=mesh)
        assert float(jnp.max(jnp.abs(got_logits - ref_logits))) < 2e-4
        assert float(jnp.max(jnp.abs(got_cache.k - ref_cache.k))) < 2e-4
        assert float(jnp.max(jnp.abs(got_cache.v - ref_cache.v))) < 2e-4

    def test_decode_continues_from_ring_cache(self):
        """Long-context serving story end-to-end: SP prefill -> gather ->
        single-device decode emits the same tokens as the dense pipeline."""
        import numpy as np

        from gofr_tpu.models import decode_step
        from gofr_tpu.parallel.ring import ring_prefill

        cfg, params, mesh, toks, lens = self._setup()
        s = toks.shape[1]
        pad = 8  # decode headroom

        ref_logits, ref_cache = prefill(params, cfg, toks, lens, s + pad)
        ring_logits, ring_cache = ring_prefill(
            params, cfg, toks, lens, mesh=mesh, max_cache_len=s + pad
        )
        ring_cache = jax.device_get(ring_cache)

        def roll(first_logits, cache, n=4):
            out = []
            tok = jnp.argmax(first_logits, axis=-1).astype(jnp.int32)
            for _ in range(n):
                out.append(np.asarray(tok).tolist())
                logits, cache = decode_step(params, cfg, tok, cache)
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return out

        assert roll(ring_logits, ring_cache) == roll(ref_logits, ref_cache)

    def test_indivisible_seq_raises(self):
        from gofr_tpu.parallel.ring import ring_prefill

        cfg, params, mesh, _toks, _lens = self._setup()
        toks = jnp.zeros((1, 60), jnp.int32)  # 60 % 8 != 0
        with pytest.raises(ValueError):
            ring_prefill(params, cfg, toks, jnp.asarray([60]), mesh=mesh)


@pytest.mark.slow  # ~40s: exhaustive window sweep, one compile per window
def test_ring_attention_sliding_window_matches_reference():
    """Banded ring attention: chunk skipping + in-chunk band masks over
    global positions must equal the reference band mask, for windows
    smaller than / equal to / spanning multiple ring chunks."""
    from gofr_tpu.parallel import make_mesh, ring_attention

    mesh = make_mesh({"seq": 8})
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k, v = (jax.random.normal(kk, (2, 64, 4, 32)) for kk in ks)
    for window in (3, 8, 20, 63):
        ref = mha_reference(q, k, v, causal=True, window=window)
        out = ring_attention(
            q, k, v, mesh=mesh, axis="seq", causal=True, window=window
        )
        assert jnp.abs(ref - out).max() < 2e-5, window


def test_ring_prefill_sliding_window_matches_plain_prefill():
    """Long-context SP prefill for the Mistral family: seq-sharded ring
    prefill logits must match the single-device windowed prefill."""
    from gofr_tpu.models import TransformerConfig, init_params, prefill
    from gofr_tpu.parallel import make_mesh, ring_prefill

    cfg = TransformerConfig.tiny_mistral()
    params = init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab_size)
    lens = jnp.asarray([32, 32], jnp.int32)
    ref, _ = prefill(params, cfg, toks, lens, 48)
    mesh = make_mesh({"seq": 8})
    out, _ = ring_prefill(params, cfg, toks, lens, mesh=mesh, max_cache_len=48)
    assert jnp.abs(ref - out).max() < 1e-3


def test_qwen2_bias_family_trains_under_pp():
    """qkv-bias layer leaves must be covered by the pipeline-parallel
    shardings (regression: the hard-coded key list omitted them)."""
    import numpy as np

    from gofr_tpu.parallel import make_pp_train_step

    cfg = TransformerConfig.tiny_qwen2()
    pmesh = jax.sharding.Mesh(np.array(jax.devices()[:2]).reshape(2), ("stage",))
    shard_fn, init_opt, step = make_pp_train_step(cfg, pmesh, n_micro=2)
    params = shard_fn(init_params(jax.random.PRNGKey(0), cfg))
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size)
    mask = jnp.ones_like(toks, dtype=bool)
    _, _, loss = step(params, init_opt(params), toks, mask)
    assert float(loss) > 0


def test_qwen2_bias_family_trains_dp_tp():
    """Bias leaves ride the DP x TP train step like any other param
    (sharded by param_specs, updated by the optimizer)."""
    from gofr_tpu.parallel import make_train_step

    cfg = TransformerConfig.tiny_qwen2()
    mesh = make_mesh({"data": 2, "model": 4})
    params = init_params(jax.random.PRNGKey(0), cfg)
    shard_fn, init_opt, step = make_train_step(cfg, mesh, learning_rate=1e-2)
    params = shard_fn(params)
    opt_state = init_opt(params)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size)
    mask = jnp.ones_like(toks, dtype=bool)
    toks, mask = place_batch((toks, mask), mesh)
    first = None
    b0 = params["layers"]["bq"]
    for _ in range(5):
        params, opt_state, loss = step(params, opt_state, toks, mask)
        first = first if first is not None else float(loss)
    assert float(loss) < first
    # the biases actually trained (optimizer touched them)
    assert float(jnp.abs(params["layers"]["bq"] - b0).max()) > 0

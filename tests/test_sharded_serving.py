"""Sharded + disaggregated serving tests (docs/advanced-guide/sharded-serving.md).

The load-bearing invariants:

- **TP == single chip.** An engine running tensor-parallel over a CPU
  submesh emits greedy token streams identical to the single-device
  engine — across the dense, paged, windowed(rolling), prefix-hit, and
  speculative slot families, with collective-compute overlap on and off
  (gathered-weight decode is bit-identical by construction; the prefill
  collectives are exact since param_specs sharded at whole-head
  granularity).
- **Disaggregated == colocated.** Splitting the fleet into prefill and
  decode role pools with KV handoff changes WHERE bytes live, never
  which tokens come back — including under concurrent mixed-length load
  (mid-prefill chunking while handoffs fly), with device-put and
  host-staged transfers (byte-identical oracle), and across
  handoff-failure failover (decode pool dead -> re-prefill on a live
  replica).
- **Elastic submesh placement.** A quarantined TP submesh no longer
  parks its replica slot when a same-size spare submesh exists — the
  supervisor rebuilds there; parking remains the (visible) behavior
  only when no spare fits.

scripts/smoke_sharded.py drives the TP fleet + disaggregated pair over
real sockets in CI."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.llm import GenRequest, LLMEngine, ReplicatedLLMEngine
from gofr_tpu.llm_disagg import DisaggregatedLLMEngine
from gofr_tpu.metrics import new_metrics_manager
from gofr_tpu.models import TransformerConfig, generate, init_params
from gofr_tpu.parallel import kv_specs, make_mesh, param_specs, tp_submeshes
from gofr_tpu.resilience import FaultInjector

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices"
)

CFG = TransformerConfig.tiny()


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _reference(params, cfg, prompt: list[int], n: int) -> list[int]:
    toks = jnp.asarray([prompt], jnp.int32)
    lens = jnp.asarray([len(prompt)], jnp.int32)
    return [int(t) for t in np.asarray(generate(params, cfg, toks, lens, n))[0]]


def _wait(pred, timeout: float, what: str = "condition") -> None:
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return
        time.sleep(0.01)
    raise AssertionError(f"timed out waiting for {what}")


_KW = dict(
    slots=4, max_seq_len=64, prefill_buckets=(8,), decode_chunk=4,
    prefill_chunk=4, step_token_budget=8, warmup=False,
)


def _tp_engine(params, tp, cfg=CFG, **kw):
    mesh = make_mesh(
        {"data": 1, "model": tp}, devices=jax.devices()[:tp]
    )
    merged = dict(_KW, **kw)
    return LLMEngine(
        cfg, params, mesh=mesh, param_specs=param_specs(cfg, mesh), **merged
    )


# ---------------------------------------------------------------------------
# sharding specs
# ---------------------------------------------------------------------------
class TestKVSpecs:
    def test_kv_sharded_when_heads_divide(self):
        P = jax.sharding.PartitionSpec
        mesh = make_mesh(
            {"data": 1, "model": 2}, devices=jax.devices()[:2]
        )
        # tiny: n_kv_heads=2, tp=2 divides -> heads axis sharded
        assert kv_specs(CFG, mesh) == P(None, None, None, "model", None)
        mesh8 = make_mesh({"data": 1, "model": 8})
        # tp=8 does not divide 2 kv heads -> replicated (the MQA rule)
        assert kv_specs(CFG, mesh8) == P(None, None, None, None, None)

    def test_tp_submeshes_carves_disjoint_pools(self):
        meshes = tp_submeshes(CFG, 2, replicas=3)
        assert len(meshes) == 3
        seen = set()
        for mesh, specs in meshes:
            devs = set(mesh.devices.flat)
            assert len(devs) == 2 and devs.isdisjoint(seen)
            seen |= devs
            assert "wq" in specs["layers"]
        with pytest.raises(ValueError):
            tp_submeshes(CFG, 4, replicas=3)  # 12 chips > 8


def test_tp_warmup_compiles_what_serving_dispatches(params):
    """Under a TP mesh the programs hand their small state back replicated
    over the mesh, and an AOT executable accepts only the shardings it
    was compiled for: a warm-up that stood in single-device zeros sent
    llm.admit_update and llm.hit_first to plain jit (a recompile in the
    serving path) on the first admission and the first prefix hit."""
    from gofr_tpu.profiling import default_registry

    eng = _tp_engine(
        params, 2, warmup=True, prefix_cache_mb=8.0, kv_label="tpwarm"
    )
    try:
        warm = default_registry().snapshot(model="tpwarm")["totals"]["compiles"]
        prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3]
        first = eng.generate(list(prompt), max_new_tokens=6)
        again = eng.generate(list(prompt), max_new_tokens=6)  # exact hit
        assert first == again == _reference(params, CFG, prompt, 6)
        assert eng.stats()["kvcache"]["prefix"]["hits"] >= 1
        snap = default_registry().snapshot(model="tpwarm")
        assert snap["degraded"] == []
        assert snap["totals"]["compiles"] == warm
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# TP == single chip, across the slot families
# ---------------------------------------------------------------------------
class TestTPTokenEquality:
    def test_paged_and_prefix_hit(self, params):
        """Paged pool + radix sharing under TP: fresh admissions AND
        exact prefix hits (second submit of a published prompt samples
        the stored logits, skipping prefill) match single-chip."""
        prompts = [[5, 9, 2, 7, 1], [3, 1, 4, 1, 5, 9, 2, 6, 5, 3], [8, 8]]
        want = [_reference(params, CFG, p, 6) for p in prompts]
        eng = _tp_engine(params, 2, prefix_cache_mb=8.0)
        try:
            assert eng.tp_degree == 2 and eng.kv.paged
            first = [eng.generate(list(p), max_new_tokens=6) for p in prompts]
            again = [eng.generate(list(p), max_new_tokens=6) for p in prompts]
            assert first == want and again == want
            st = eng.stats()["kvcache"]["prefix"]
            assert st["hits"] >= len(prompts)  # second pass exact-hit
        finally:
            eng.close()

    def test_dense_overlap_on_and_off(self, params):
        """Contiguous (kv_paged=False) TP decode with collective-compute
        overlap on and off — both must equal single-chip greedy."""
        prompt = [5, 9, 2, 7, 1, 3, 4]
        want = _reference(params, CFG, prompt, 8)
        for overlap in (True, False):
            eng = _tp_engine(
                params, 2, kv_paged=False, tp_overlap=overlap,
            )
            try:
                assert eng.tp_overlap is overlap
                assert eng.generate(list(prompt), max_new_tokens=8) == want
            finally:
                eng.close()

    def test_windowed_rolling(self, params):
        """Sliding-window model (rolling-ring slots) under TP: the kv
        heads (2) divide tp=2, so the ring itself is head-sharded."""
        cfg = TransformerConfig.tiny_mistral()
        wparams = init_params(jax.random.PRNGKey(0), cfg)
        prompt = [7, 3, 9, 1, 4, 4, 2, 8, 6, 5, 1, 2]
        want = _reference(wparams, cfg, prompt, 6)
        eng = _tp_engine(wparams, 2, cfg=cfg)
        try:
            assert eng.kv.rolling
            assert eng.generate(list(prompt), max_new_tokens=6) == want
        finally:
            eng.close()

    @pytest.mark.parametrize("layout", ["ring", "slab"])
    def test_contiguous_stack_written_in_place(self, params, layout):
        """The contiguous stack under TP is sharded on its kv heads, and a
        step writes its rows into it by a scatter that indexes (layer,
        slot, row) alone: three requests at once (packed rows, padding
        lanes, last rows shorter than their chunk), prompts that take the
        ring of 12 round three times, equal single-chip greedy; the stack
        keeps the sharding it was committed with."""
        cfg = TransformerConfig.tiny_mistral() if layout == "ring" else CFG
        prm = init_params(jax.random.PRNGKey(0), cfg) if layout == "ring" else params
        rng = np.random.default_rng(34)
        prompts = [rng.integers(1, cfg.vocab_size, n).tolist() for n in (38, 5, 23)]
        want = [_reference(prm, cfg, p, 5 + i) for i, p in enumerate(prompts)]
        eng = _tp_engine(prm, 2, cfg=cfg, kv_paged=False)
        try:
            assert eng.kv.rolling == (layout == "ring") and not eng.kv.paged
            outs: list = [None] * len(prompts)

            def run(i):
                outs[i] = eng.generate(list(prompts[i]), max_new_tokens=5 + i)

            threads = [threading.Thread(target=run, args=(i,)) for i in range(len(prompts))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert outs == want
            assert eng.cache.k.sharding.is_equivalent_to(eng._kv_sharding, eng.cache.k.ndim)
        finally:
            eng.close()

    def test_speculative(self, params):
        """Spec-on TP engine == spec-off single chip (greedy): the fused
        verify program runs against the sharded pool through the same
        gather/scatter family as decode."""
        prompt = [1, 2, 3, 1, 2, 3, 1, 2, 3, 1, 2]  # n-gram drafter food
        want = _reference(params, CFG, prompt, 10)
        eng = _tp_engine(params, 2, speculative=True, max_seq_len=96)
        try:
            got = eng.generate(list(prompt), max_new_tokens=10)
            assert got == want
        finally:
            eng.close()

    def test_fleet_of_tp_submeshes_load_accounting(self, params):
        """dp x tp fleet: token-weighted routing signals settle back to
        zero after the work drains on every TP replica (the load/
        fairness accounting parity the router depends on)."""
        rep = ReplicatedLLMEngine(
            CFG, params, meshes=tp_submeshes(CFG, 2, replicas=2),
            supervise=False, **_KW,
        )
        try:
            prompts = [[5, 9, 2], [7, 1], [3, 3, 4, 1], [11, 2, 6, 1, 9]]
            reqs = [
                rep.submit(GenRequest(list(p), max_new_tokens=5))
                for p in prompts
            ]
            outs = [r.tokens() for r in reqs]
            for p, got in zip(prompts, outs):
                assert got == _reference(params, CFG, p, 5)
            _wait(
                lambda: rep.load_tokens() == 0 and rep.load() == 0,
                10, "load drains to zero",
            )
            for e in rep.engines:
                assert e.tp_degree == 2
                assert e.load_tokens() == 0 and e.resident_slots() == 0
        finally:
            rep.close()


# ---------------------------------------------------------------------------
# KV handoff primitives
# ---------------------------------------------------------------------------
class TestHandoffPrimitives:
    def test_export_import_roundtrip_exact_hit(self, params):
        kw = dict(_KW, prefix_cache_mb=8.0)
        src = LLMEngine(CFG, params, kv_label="src", **kw)
        dst = LLMEngine(CFG, params, kv_label="dst", **kw)
        try:
            prompt = [5, 9, 2, 7, 1, 3]
            want = _reference(params, CFG, prompt, 8)
            src.submit(GenRequest(
                list(prompt), max_new_tokens=1, temperature=0.0,
                eos_token=-1,
            )).tokens()
            payload = src.kv_handoff_export(prompt, timeout=15)
            assert payload is not None
            assert payload["n_full"] * src.kv.block + payload["tail_len"] == len(prompt)
            # host-staged transfer (the byte-identical oracle)
            payload = {
                k: (np.asarray(v) if hasattr(v, "shape") else v)
                for k, v in payload.items()
            }
            assert dst.kv_handoff_import(payload, timeout=15)
            got = dst.generate(list(prompt), max_new_tokens=8)
            assert got == want
            # the import made it an EXACT radix hit — prefill skipped
            assert dst.stats()["kvcache"]["prefix"]["hits"] >= 1
        finally:
            src.close()
            dst.close()

    def test_export_unpublished_prompt_is_none(self, params):
        eng = LLMEngine(CFG, params, prefix_cache_mb=8.0, **_KW)
        try:
            assert eng.kv_handoff_export([1, 2, 3], timeout=15) is None
        finally:
            eng.close()

    def test_export_on_unpaged_engine_is_none(self, params):
        eng = LLMEngine(CFG, params, kv_paged=False, **_KW)
        try:
            assert eng.kv_handoff_export([1, 2, 3]) is None
            assert not eng.kv_handoff_import({"k": np.zeros((2, 1, 16, 2, 16))})
        finally:
            eng.close()


# ---------------------------------------------------------------------------
# disaggregated == colocated
# ---------------------------------------------------------------------------
class TestDisaggregated:
    def _control(self, params, **kw):
        merged = dict(_KW, **kw)
        return LLMEngine(CFG, params, **merged)

    def test_matches_colocated_under_mixed_load(self, params):
        """Concurrent mixed short/long prompts through a 1-prefill +
        1-decode pair: long prompts take several prefill chunks
        (prefill_chunk=4), so handoffs overlap live mid-prefill work on
        the prefill replica — greedy bodies must equal the colocated
        engine's exactly, and the handoff path must actually engage."""
        prompts = [
            [5, 9, 2, 7],
            list(range(1, 25)),  # 24 tokens -> 6 prefill chunks
            [8, 8, 1],
            list(range(30, 50)),  # 20 tokens -> 5 chunks
            [3, 1, 4, 1, 5],
            [2] * 16,
        ]
        ctrl = self._control(params)
        want = [ctrl.generate(list(p), max_new_tokens=6) for p in prompts]
        ctrl.close()
        metrics = new_metrics_manager()
        eng = DisaggregatedLLMEngine(
            CFG, params, replicas=2, prefill_replicas=1,
            supervise=False, metrics=metrics, **_KW,
        )
        try:
            reqs = [
                eng.submit(GenRequest(list(p), max_new_tokens=6))
                for p in prompts
            ]
            got = [r.tokens(timeout=120) for r in reqs]
            assert got == want
            st = eng.stats()
            assert st["handoff"]["ok"] == len(prompts)
            assert st["handoff"]["miss"] == 0
            assert st["prefill"]["per_replica"][0]["submitted"] == len(prompts)
            assert st["decode"]["per_replica"][0]["submitted"] == len(prompts)
            # decode admissions were exact radix hits on transferred KV
            dec_prefix = st["decode"]["per_replica"][0]["kvcache"]["prefix"]
            assert dec_prefix["hits"] == len(prompts)
            # per-role latency series landed
            expo = metrics.render_prometheus()
            assert "app_llm_kv_handoff_seconds" in expo
            assert 'role="prefill"' in expo and 'role="decode"' in expo
            assert "app_llm_collective_seconds" in expo
        finally:
            eng.close()

    def test_d2d_and_host_staged_byte_identical(self, params):
        """TPU_LLM_KV_HANDOFF_D2D=0 (host-staged numpy) and the
        device-put path must produce identical greedy bodies — the
        transfer is bytes either way."""
        prompt = list(range(1, 20))
        ctrl = self._control(params)
        want = ctrl.generate(list(prompt), max_new_tokens=8)
        ctrl.close()
        for d2d in (True, False):
            eng = DisaggregatedLLMEngine(
                CFG, params, replicas=2, prefill_replicas=1,
                supervise=False, handoff_d2d=d2d, **_KW,
            )
            try:
                got = eng.generate(list(prompt), max_new_tokens=8)
                assert got == want, f"d2d={d2d}"
                assert eng.handoffs_ok == 1
            finally:
                eng.close()

    def test_decode_pool_dead_reprefills_on_live_replica(self, params):
        """Handoff-failure failover: with the whole decode pool dead the
        request re-prefills colocated on a live prefill replica —
        token-identical, counted as a fallback, never an error."""
        prompt = [5, 9, 2, 7, 1, 3, 8]
        ctrl = self._control(params)
        want = ctrl.generate(list(prompt), max_new_tokens=6)
        ctrl.close()
        eng = DisaggregatedLLMEngine(
            CFG, params, replicas=2, prefill_replicas=1,
            supervise=False, **_KW,
        )
        try:
            eng.decode.engines[0]._die("injected for handoff-failover test")
            _wait(
                lambda: not eng.decode.engines[0].alive(), 10,
                "decode replica death",
            )
            got = eng.generate(list(prompt), max_new_tokens=6)
            assert got == want
            assert eng.fallbacks >= 1
        finally:
            eng.close()

    def test_handoff_timeout_degrades_to_reprefill(self, params):
        """An export that cannot complete within the timeout must cost
        latency only: the decode pool re-prefills and the stream stays
        token-identical."""
        prompt = [5, 9, 2, 7, 1]
        ctrl = self._control(params)
        want = ctrl.generate(list(prompt), max_new_tokens=6)
        ctrl.close()
        eng = DisaggregatedLLMEngine(
            CFG, params, replicas=2, prefill_replicas=1,
            supervise=False, **_KW,
        )
        try:
            peng = eng.prefill.engines[0]
            orig = peng.kv_handoff_export
            peng.kv_handoff_export = lambda *a, **k: (_ for _ in ()).throw(
                TimeoutError("forced (test)")
            )
            got = eng.generate(list(prompt), max_new_tokens=6)
            assert got == want
            assert eng.handoffs_miss >= 1 and eng.handoffs_ok == 0
            peng.kv_handoff_export = orig
        finally:
            eng.close()

    def test_sessions_route_colocated_to_decode_pool(self, params):
        """Session turns ride the decode pool's affinity machinery (the
        conversation KV is published there); bodies stay correct."""
        eng = DisaggregatedLLMEngine(
            CFG, params, replicas=2, prefill_replicas=1,
            supervise=False, session_mb=16.0, **_KW,
        )
        try:
            prompt = [5, 9, 2, 7]
            want = _reference(params, CFG, prompt, 5)
            got = eng.submit(GenRequest(
                list(prompt), max_new_tokens=5, session_id="conv-1",
            )).tokens(timeout=60)
            assert got == want
            # served by the decode pool, not the prefill probes
            assert eng.decode.engines[0].submitted == 1
            assert eng.prefill.engines[0].submitted == 0
        finally:
            eng.close()

    def test_shared_fairness_ledger_across_pools(self, params):
        """ONE fairness ledger spans both role pools — per-client
        weighted ordering must not reset at the role boundary."""
        eng = DisaggregatedLLMEngine(
            CFG, params, replicas=2, prefill_replicas=1,
            supervise=False, **_KW,
        )
        try:
            assert eng.prefill.ledger is not None
            assert eng.prefill.ledger is eng.decode.ledger
            got = eng.submit(GenRequest(
                [5, 9, 2], max_new_tokens=4, client="alice",
            )).tokens(timeout=60)
            assert got == _reference(params, CFG, [5, 9, 2], 4)
            snap = eng.prefill.ledger.snapshot()
            # the prompt billed on the prefill pool and the decode billed
            # on the decode pool both land on ONE per-client counter
            assert "alice" in snap["counters"]
            _wait(
                lambda: eng.load_tokens() == 0, 10,
                "disagg load drains to zero",
            )
        finally:
            eng.close()

    def test_rejects_unpaged(self, params):
        with pytest.raises(ValueError):
            DisaggregatedLLMEngine(
                CFG, params, replicas=2, kv_paged=False, **_KW
            )

    def test_rejects_shared_whole_slice_mesh(self, params):
        """A single mesh/param_specs pair forwarded to every replica
        would put both role pools on the same chips (the split a no-op,
        the handoff a self-transfer) — refused at construction; TP
        disaggregation takes meshes=[...] of disjoint submeshes."""
        mesh = make_mesh({"data": 1, "model": 8})
        with pytest.raises(ValueError):
            DisaggregatedLLMEngine(
                CFG, params, replicas=2,
                mesh=mesh, param_specs=param_specs(CFG, mesh), **_KW,
            )

    def test_deploy_refused_loudly(self, params):
        """ModelHandle.deploy dispatches on hasattr(engine, 'deploy'):
        without an explicit refusal the bare-engine swap rollout would
        silently replace the whole disaggregated topology with one
        default single-chip engine."""
        from gofr_tpu.resilience.rollout import RolloutError

        eng = DisaggregatedLLMEngine(
            CFG, params, replicas=2, prefill_replicas=1,
            supervise=False, **_KW,
        )
        try:
            with pytest.raises(RolloutError):
                eng.deploy(CFG, params)
        finally:
            eng.close()


# ---------------------------------------------------------------------------
# elastic submesh placement
# ---------------------------------------------------------------------------
class TestElasticSubmesh:
    def _fleet(self, params, inj, meshes, **kw):
        merged = dict(_KW, slots=2, **kw)
        return ReplicatedLLMEngine(
            CFG, params, meshes=meshes, fault_injector=inj, **merged
        )

    def test_quarantined_submesh_rebuilds_on_spare(self, params, monkeypatch):
        """2 x tp=2 replicas over 4 chips, 4 spare chips: when replica
        0's home submesh quarantines, the supervisor rebuilds it on a
        spare same-size submesh instead of parking (the PR 7 behavior
        this PR retires) — placement changes, tokens do not."""
        monkeypatch.setenv("TPU_LLM_SUPERVISOR_INTERVAL_S", "0.05")
        monkeypatch.setenv("TPU_LLM_RESTART_BACKOFF_S", "0.05")
        monkeypatch.setenv("TPU_LLM_DEVICE_QUARANTINE_FAILURES", "1")
        monkeypatch.setenv("TPU_LLM_DEVICE_COOLDOWN_S", "60")
        inj = FaultInjector()
        rep = self._fleet(
            params, inj, tp_submeshes(CFG, 2, replicas=2), supervise=True,
        )
        try:
            home = rep._device_keys[0]
            corpse = rep.engines[0]
            inj.arm("replica_kill", label="/r0")
            _wait(lambda: not corpse.alive(), 10, "replica 0 death")
            # one classified death trips quarantine (failures=1): the
            # home submesh is out, placement must move
            _wait(
                lambda: rep.health.state(home) == "quarantined", 30,
                "home submesh quarantine",
            )
            _wait(
                lambda: rep.engines[0] is not corpse
                and rep.engines[0].alive(),
                60, "elastic submesh rebuild",
            )
            landed = rep._current_keys[0]
            assert landed != home
            landed_devs = set(landed.split("+"))
            home_devs = set(home.split("+"))
            peer_devs = set(rep._current_keys[1].split("+"))
            assert landed_devs.isdisjoint(home_devs)
            assert landed_devs.isdisjoint(peer_devs)
            assert len(landed_devs) == 2  # same-size submesh
            assert rep.engines[0].tp_degree == 2
            toks = rep.engines[0].generate([5, 9, 2], max_new_tokens=4)
            assert toks == _reference(params, CFG, [5, 9, 2], 4)
            assert (rep.supervisor.parked_count() if rep.supervisor else 0) == 0
        finally:
            inj.disarm()
            rep.close()

    def test_parks_when_no_spare_submesh(self, params, monkeypatch):
        """2 x tp=4 replicas cover all 8 chips: a quarantined submesh
        has nowhere to go — the slot parks (visible capacity
        degradation), pinned exactly as before."""
        monkeypatch.setenv("TPU_LLM_SUPERVISOR_INTERVAL_S", "0.05")
        monkeypatch.setenv("TPU_LLM_RESTART_BACKOFF_S", "0.05")
        monkeypatch.setenv("TPU_LLM_DEVICE_QUARANTINE_FAILURES", "1")
        monkeypatch.setenv("TPU_LLM_DEVICE_COOLDOWN_S", "60")
        inj = FaultInjector()
        rep = self._fleet(
            params, inj, tp_submeshes(CFG, 4, replicas=2), supervise=True,
        )
        try:
            home = rep._device_keys[0]
            corpse = rep.engines[0]
            inj.arm("replica_kill", label="/r0")
            _wait(lambda: not corpse.alive(), 10, "replica 0 death")
            _wait(
                lambda: rep.health.state(home) == "quarantined", 30,
                "home submesh quarantine",
            )
            _wait(
                lambda: rep.supervisor.parked_count() == 1, 30,
                "slot parks (no spare submesh)",
            )
            assert not rep.engines[0].alive()
            # the survivor keeps serving token-identically
            toks = rep.engines[1].generate([5, 9, 2], max_new_tokens=4)
            assert toks == _reference(params, CFG, [5, 9, 2], 4)
            assert rep.stats()["replicas_parked"] == 1
        finally:
            inj.disarm()
            rep.close()

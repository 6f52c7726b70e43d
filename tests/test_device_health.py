"""Device-health tests: failure ledger + quarantine state machine,
canary gate, numerical watchdog, poison-request quarantine, elastic
rebuild, parking, and reintegration.

The load-bearing invariants extend test_resilience's: device judgment
may change PLACEMENT, never RESULTS — a replica rebuilt on an alternate
device serves token-identical greedy streams; a poison payload's blast
radius is bounded to TPU_LLM_POISON_DEATHS replicas while concurrent
streams survive token-identically; and non-finite logits become a
classified replica death instead of a garbage stream with status 200.

Every fault here is deterministic (gofr_tpu.resilience.faults);
scripts/smoke_quarantine.py drives the quarantine/park/reintegrate loop
over real sockets in CI."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.llm import (
    GenRequest,
    LLMEngine,
    PoisonedRequestError,
    ReplicatedLLMEngine,
)
from gofr_tpu.llm_programs import finite_guard
from gofr_tpu.metrics import new_metrics_manager
from gofr_tpu.models import TransformerConfig, generate, init_params
from gofr_tpu.resilience import (
    DeviceHealthLedger,
    FaultInjector,
    canary_check,
    device_key,
    spec_device_key,
)
from gofr_tpu.resilience.health import CANARY_MAX_NEW, CANARY_PROMPT

CFG = TransformerConfig.tiny()


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _reference_tokens(params, prompt: list[int], n: int) -> list[int]:
    toks = jnp.asarray([prompt], jnp.int32)
    lens = jnp.asarray([len(prompt)], jnp.int32)
    out = generate(params, CFG, toks, lens, n)
    return [int(t) for t in np.asarray(out)[0]]


def _wait(pred, timeout: float, what: str = "condition") -> None:
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return
        time.sleep(0.01)
    raise AssertionError(f"timed out waiting for {what}")


def _fleet(params, inj, *, replicas=2, supervise=False, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_seq_len", 128)
    kw.setdefault("prefill_buckets", (8,))
    kw.setdefault("prefill_chunk", 4)
    kw.setdefault("step_token_budget", 4)
    kw.setdefault("decode_chunk", 2)
    kw.setdefault("lookahead", 1)
    kw.setdefault("warmup", False)
    return ReplicatedLLMEngine(
        CFG, params, replicas=replicas, fault_injector=inj,
        supervise=supervise, **kw,
    )


# ---------------------------------------------------------------------------
# ledger unit behavior (fake clock)
# ---------------------------------------------------------------------------
class TestLedger:
    def _ledger(self, clock, **kw):
        kw.setdefault("failures", 3)
        kw.setdefault("window_s", 10.0)
        kw.setdefault("cooldown_s", 5.0)
        return DeviceHealthLedger(now_fn=lambda: clock["t"], **kw)

    def test_quarantine_after_k_failures_in_window(self):
        clock = {"t": 0.0}
        led = self._ledger(clock)
        assert not led.record_failure("cpu:0", "step_fault")
        assert not led.record_failure("cpu:0", "watchdog_hang")
        assert led.state("cpu:0") == "healthy" and led.usable("cpu:0")
        assert led.record_failure("cpu:0", "rebuild_failure")
        assert led.state("cpu:0") == "quarantined"
        assert not led.usable("cpu:0")
        assert led.quarantines == 1
        # other devices unaffected
        assert led.state("cpu:1") == "healthy"

    def test_failures_outside_window_age_out(self):
        clock = {"t": 0.0}
        led = self._ledger(clock)
        led.record_failure("cpu:0", "step_fault")
        led.record_failure("cpu:0", "step_fault")
        clock["t"] = 11.0  # both events now older than window_s
        assert not led.record_failure("cpu:0", "step_fault")
        assert led.state("cpu:0") == "healthy"

    def test_cooldown_probation_reintegration(self):
        clock = {"t": 0.0}
        led = self._ledger(clock, failures=1)
        led.record_failure("cpu:0", "numerical")
        assert led.state("cpu:0") == "quarantined"
        clock["t"] = 5.1  # cooldown served
        assert led.state("cpu:0") == "probation"
        assert led.usable("cpu:0")  # a probe rebuild may target it
        assert led.quarantined_count() == 1  # but it has not proven itself
        led.probe_ok("cpu:0")
        assert led.state("cpu:0") == "healthy"
        assert led.quarantined_count() == 0

    def test_failure_while_quarantined_escalates_cooldown(self):
        clock = {"t": 0.0}
        led = self._ledger(clock, failures=1)
        led.record_failure("cpu:0", "step_fault")  # trip; cooldown 5
        clock["t"] = 5.1  # probation
        assert led.record_failure("cpu:0", "rebuild_failure")  # failed probe
        # re-trip with doubled cooldown from the re-trip time
        assert led.state("cpu:0") == "quarantined"
        clock["t"] = 5.1 + 5.0
        assert led.state("cpu:0") == "quarantined", "cooldown did not double"
        clock["t"] = 5.1 + 10.1
        assert led.state("cpu:0") == "probation"

    def test_classify(self):
        c = DeviceHealthLedger.classify
        assert c("step watchdog: fetch:chunk exceeded 0.3s") == "watchdog_hang"
        assert c("numerical watchdog: non-finite logits (decode chunk)") == "numerical"
        assert c("canary rejected: diverged") == "rebuild_failure"
        assert c("device_sick: build refused on cpu:0") == "rebuild_failure"
        assert c("fault injection: replica_kill") == "step_fault"
        assert c("scheduler thread exited unexpectedly") == "step_fault"
        assert c(None) == "unknown"

    def test_metrics_and_snapshot(self):
        clock = {"t": 0.0}
        metrics = new_metrics_manager()
        from gofr_tpu.resilience import register_resilience_metrics

        register_resilience_metrics(metrics)
        led = DeviceHealthLedger(
            failures=1, window_s=10, cooldown_s=5,
            now_fn=lambda: clock["t"], metrics=metrics, model="m",
        )
        led.record_failure("cpu:3", "numerical", detail="nan in decode")
        assert metrics.gauge_total("app_llm_devices_quarantined") == 1.0
        snap = led.snapshot()
        assert snap["quarantines"] == 1
        assert snap["devices"]["cpu:3"]["state"] == "quarantined"
        assert snap["devices"]["cpu:3"]["by_reason"] == {"numerical": 1}
        assert snap["devices"]["cpu:3"]["cooldown_remaining_s"] > 0
        expo = metrics.render_prometheus()
        assert "app_llm_device_quarantines_total" in expo
        led.probe_ok("cpu:3")
        assert metrics.gauge_total("app_llm_devices_quarantined") == 0.0


class TestDeviceKeys:
    def test_device_and_spec_keys(self):
        devs = jax.devices()
        assert device_key(devs[0]) == f"{devs[0].platform}:{devs[0].id}"
        assert spec_device_key({"device": devs[1]}) == device_key(devs[1])

    def test_mesh_spec_key_is_one_health_unit(self):
        from gofr_tpu.parallel import make_mesh

        n = len(jax.devices())
        mesh = make_mesh({"data": 1, "model": n})
        key = spec_device_key({"mesh": mesh, "param_specs": {}})
        assert "+" in key and key.count(":") == n


# ---------------------------------------------------------------------------
# fault-injector extensions: @label env syntax, tagged specs
# ---------------------------------------------------------------------------
class TestFaultExtensions:
    def test_env_arming_with_device_label(self):
        from gofr_tpu.resilience.faults import _arm_from_env

        inj = FaultInjector()
        _arm_from_env(inj, "device_sick=3@cpu:0,nan_logits=1")
        snap = inj.snapshot()
        assert snap["armed"]["device_sick"][0] == {
            "count": 3, "label": "cpu:0", "delay": 0.0,
        }
        assert snap["armed"]["nan_logits"][0]["label"] is None
        assert inj.take("device_sick", "cpu:1") is None
        assert inj.take("device_sick", "cpu:0") is not None

    def test_tagged_specs_are_a_disjoint_population(self):
        inj = FaultInjector()
        inj.arm("device_step", tag="boom", count=-1)
        inj.arm("device_step", count=1)
        # untagged take never consumes the tagged spec, and vice versa
        assert inj.take("device_step", "llm", tag="other") is None
        assert inj.take("device_step", "llm").tag is None
        assert inj.take("device_step", "llm") is None  # untagged exhausted
        assert inj.take("device_step", "llm", tag="boom").tag == "boom"
        assert inj.has_tagged("device_step")
        inj.disarm()
        assert not inj.has_tagged("device_step")


# ---------------------------------------------------------------------------
# numerical watchdog: NaN/Inf logits -> classified replica death
# ---------------------------------------------------------------------------
class TestNumericalWatchdog:
    def test_finite_guard_sentinel(self):
        logits = jnp.asarray([
            [0.1, 0.9, 0.3],
            [float("nan"), 0.2, 0.1],
            [0.5, float("inf"), 0.2],
            [0.4, 0.1, 0.2],
        ])
        toks = jnp.asarray([1, 1, 1, 0], jnp.int32)
        out = np.asarray(finite_guard(logits, toks))
        assert out.tolist() == [1, -1, -1, 0]

    def test_nan_logits_kills_engine_with_numerical_reason(self, params):
        inj = FaultInjector()
        metrics = new_metrics_manager()
        eng = LLMEngine(
            CFG, params, slots=2, max_seq_len=64, prefill_buckets=(8,),
            prefill_chunk=4, step_token_budget=4, decode_chunk=2,
            warmup=False, fault_injector=inj, metrics=metrics,
        )
        try:
            assert eng.numeric_check  # default on
            req = eng.submit(GenRequest([5, 9, 2], max_new_tokens=8))
            _wait(lambda: req.emitted > 0, 30, "first token")
            inj.arm("nan_logits")
            toks = req.tokens(timeout=30)  # unblocked, not a 60s hang
            assert -1 not in toks, "sentinel leaked into the stream"
            _wait(lambda: not eng.alive(), 10, "numerical death")
            assert (eng.died_reason or "").startswith("numerical watchdog")
            assert eng.numerical_trips == 1
            assert "app_llm_numerical_trips_total" in metrics.render_prometheus()
        finally:
            eng.close()

    def test_nan_logits_fails_over_token_identical(self, params):
        inj = FaultInjector()
        rep = _fleet(params, inj)
        try:
            prompt = [5, 9, 2, 11]
            want = _reference_tokens(params, prompt, 24)
            req = GenRequest(list(prompt), max_new_tokens=24)
            rep.engines[0].submit(req)
            _wait(lambda: req.emitted > 0, 30, "first token")
            inj.arm("nan_logits", label="/r0")
            got = req.tokens(timeout=60)
            assert got == want, "post-NaN failover stream diverged"
            assert not rep.engines[0].alive()
            assert (rep.engines[0].died_reason or "").startswith(
                "numerical watchdog"
            )
            assert rep.failovers >= 1
        finally:
            rep.close()

    def test_disabled_watchdog_streams_garbage_with_200(self, params):
        # the failure mode the watchdog exists to prevent, pinned so the
        # default stays honest: with TPU_LLM_NUMERIC_CHECK=0 a NaN step
        # streams its sentinel/garbage to the caller and nothing dies
        inj = FaultInjector()
        eng = LLMEngine(
            CFG, params, slots=2, max_seq_len=64, prefill_buckets=(8,),
            prefill_chunk=4, step_token_budget=4, decode_chunk=2,
            warmup=False, fault_injector=inj, numeric_check=False,
        )
        try:
            req = eng.submit(GenRequest([5, 9, 2], max_new_tokens=8))
            _wait(lambda: req.emitted > 0, 30, "first token")
            inj.arm("nan_logits")
            toks = req.tokens(timeout=30)
            assert -1 in toks, "corruption did not reach the stream"
            assert eng.alive()
            assert eng.numerical_trips == 0
        finally:
            eng.close()


# ---------------------------------------------------------------------------
# poison-request quarantine: blast radius bounded to 2 replicas
# ---------------------------------------------------------------------------
class TestPoisonQuarantine:
    def test_poison_bounded_to_two_deaths_fleet_survives(self, params):
        inj = FaultInjector()
        metrics = new_metrics_manager()
        rep = _fleet(params, inj, replicas=3, metrics=metrics)
        try:
            prompt = [5, 9, 2, 11, 7, 3]
            want = _reference_tokens(params, prompt, 32)
            victim = GenRequest(list(prompt), max_new_tokens=32)
            rep.engines[0].submit(victim)  # innocent bystander, same replica
            _wait(lambda: victim.emitted > 0, 30, "bystander decoding")
            poison = GenRequest([1, 2, 3, 4], max_new_tokens=8, tag="boom")
            inj.arm("device_step", tag="boom", count=-1)  # reliably fatal
            rep.engines[0].submit(poison)
            with pytest.raises(PoisonedRequestError):
                poison.tokens(timeout=60)
            assert poison.finish_reason == "poison"
            assert poison.deaths == 2, "blast radius != 2 replicas"
            dead = sum(1 for e in rep.engines if not e.alive())
            assert dead == 2, f"poison killed {dead} replicas, wanted 2"
            # the fleet survives and the bystander's greedy stream is
            # token-identical across its rescue(s)
            got = victim.tokens(timeout=60)
            assert got == want, "bystander stream diverged"
            assert rep.poisoned == 1
            assert rep.stats()["poisoned"] == 1
            assert "app_llm_poison_requests_total" in metrics.render_prometheus()
            # survivor still serves fresh traffic
            toks = rep.generate([7, 7, 7], max_new_tokens=4)
            assert toks == _reference_tokens(params, [7, 7, 7], 4)
        finally:
            inj.disarm()
            rep.close()

    def test_poison_disabled_exhausts_retries_as_error(self, params):
        inj = FaultInjector()
        rep = _fleet(params, inj, replicas=3, poison_deaths=0)
        try:
            poison = GenRequest([1, 2, 3, 4], max_new_tokens=8, tag="boom")
            inj.arm("device_step", tag="boom", count=-1)
            rep.engines[0].submit(poison)
            toks = poison.tokens(timeout=60)  # no raise: legacy error path
            assert poison.finish_reason in ("error", "cancelled")
            assert len(toks) < 8
            # unbounded by the quarantine, bounded only by retry budget:
            # strictly more than 2 deaths — the motivation for the default
            assert poison.deaths > 2
        finally:
            inj.disarm()
            rep.close()


# ---------------------------------------------------------------------------
# canary gate: a half-sick rebuild never enters routing
# ---------------------------------------------------------------------------
class TestCanaryGate:
    def test_canary_rejects_token_divergent_candidate(self, params):
        ref_eng = LLMEngine(
            CFG, params, slots=2, max_seq_len=64, prefill_buckets=(8,),
            warmup=False,
        )
        # "half-sick rebuild": correct shapes, corrupted compute — an
        # unembed table shifted one row (what a wrong-offset HBM read
        # looks like to a greedy probe). Merely re-seeded random weights
        # would not do: tiny random models degenerately echo the last
        # prompt token, and tied-embedding corruptions cancel out.
        sick_params = dict(params)
        sick_params["unembed"] = jnp.roll(params["embed"], 1, axis=0)
        sick = LLMEngine(
            CFG, sick_params, slots=2,
            max_seq_len=64, prefill_buckets=(8,), warmup=False,
        )
        try:
            ok, detail, ref = canary_check(ref_eng)
            assert ok and len(ref) == CANARY_MAX_NEW
            ok2, detail2, _ = canary_check(ref_eng, ref)
            assert ok2, f"self-comparison failed: {detail2}"
            ok3, detail3, _ = canary_check(sick, ref)
            assert not ok3
            assert "diverged" in detail3
            # without a reference the divergent engine passes shape
            # checks — exactly why the fleet caches a reference
            ok4, _, _ = canary_check(sick, None)
            assert ok4
        finally:
            ref_eng.close()
            sick.close()

    def test_canary_rejects_incomplete_stream(self):
        class StubEngine:
            cfg = CFG

            def submit(self, req):
                req.out.put([1, 2])
                req.out.put(None)
                return req

        ok, detail, toks = canary_check(StubEngine())
        assert not ok and "incomplete" in detail and toks == [1, 2]

    def test_supervisor_keeps_canary_rejected_replica_out(
        self, params, monkeypatch
    ):
        monkeypatch.setenv("TPU_LLM_SUPERVISOR_INTERVAL_S", "0.05")
        monkeypatch.setenv("TPU_LLM_RESTART_BACKOFF_S", "0.05")
        monkeypatch.setenv("TPU_LLM_DEVICE_QUARANTINE_FAILURES", "100")
        inj = FaultInjector()
        rep = _fleet(params, inj, supervise=True)
        try:
            real = rep._canary_check
            rejections = []

            def gate(replacement):
                if not rejections:
                    rejections.append(1)
                    return False, "forced divergence (test)"
                return real(replacement)

            monkeypatch.setattr(rep, "_canary_check", gate)
            corpse = rep.engines[0]
            inj.arm("replica_kill", label="/r0")
            _wait(lambda: not corpse.alive(), 10, "replica 0 death")
            _wait(
                lambda: rep.engines[0] is not corpse and rep.engines[0].alive(),
                60, "post-canary restart",
            )
            assert rep.supervisor.canary_rejects == 1
            assert rep.supervisor.restarts == 1
            # the rejected rebuild was billed to the device ledger
            home = rep._device_keys[0]
            snap = rep.health.snapshot()["devices"].get(home, {})
            assert snap.get("by_reason", {}).get("rebuild_failure", 0) >= 1
            toks = rep.engines[0].generate([5, 9, 2], max_new_tokens=4)
            assert toks == _reference_tokens(params, [5, 9, 2], 4)
        finally:
            rep.close()


# ---------------------------------------------------------------------------
# elastic rebuild + quarantine + parking + reintegration
# ---------------------------------------------------------------------------
class TestElasticRebuild:
    def test_sick_device_quarantined_rebuild_lands_on_alternate(
        self, params, monkeypatch
    ):
        monkeypatch.setenv("TPU_LLM_SUPERVISOR_INTERVAL_S", "0.05")
        monkeypatch.setenv("TPU_LLM_RESTART_BACKOFF_S", "0.05")
        monkeypatch.setenv("TPU_LLM_DEVICE_QUARANTINE_FAILURES", "2")
        monkeypatch.setenv("TPU_LLM_DEVICE_QUARANTINE_WINDOW_S", "60")
        monkeypatch.setenv("TPU_LLM_DEVICE_COOLDOWN_S", "60")
        inj = FaultInjector()
        metrics = new_metrics_manager()
        rep = _fleet(params, inj, supervise=True, metrics=metrics)
        try:
            home = rep._device_keys[0]
            used = set(rep._device_keys)
            corpse = rep.engines[0]
            # the home chip is persistently sick: every rebuild on it
            # fails until quarantine reroutes placement
            inj.arm("device_sick", label=home, count=-1)
            inj.arm("replica_kill", label="/r0")
            _wait(lambda: not corpse.alive(), 10, "replica 0 death")
            # death (step_fault) + 1 failed rebuild = 2 failures -> the
            # device quarantines within K attempts, NOT an infinite loop
            _wait(
                lambda: rep.health.state(home) == "quarantined", 30,
                "home device quarantine",
            )
            _wait(
                lambda: rep.engines[0] is not corpse and rep.engines[0].alive(),
                60, "elastic rebuild",
            )
            landed = rep._current_keys[0]
            assert landed != home and landed not in used, landed
            assert rep.health.state(home) == "quarantined"
            # placement changed, results did not
            toks = rep.engines[0].generate([5, 9, 2], max_new_tokens=4)
            assert toks == _reference_tokens(params, [5, 9, 2], 4)
            st = rep.stats()
            assert st["replicas_alive"] == 2
            assert st["devices_quarantined"] == 1
            assert metrics.gauge_total("app_llm_devices_quarantined") == 1.0
            expo = metrics.render_prometheus()
            assert "app_llm_device_quarantines_total" in expo
            dbg = rep.debug_state()
            assert dbg["health"]["devices"][home]["state"] == "quarantined"
            assert dbg["devices"]["current"][0] == landed
        finally:
            inj.disarm()
            rep.close()

    def test_no_alternate_parks_then_reintegrates(self, params, monkeypatch):
        monkeypatch.setenv("TPU_LLM_SUPERVISOR_INTERVAL_S", "0.05")
        monkeypatch.setenv("TPU_LLM_RESTART_BACKOFF_S", "0.05")
        monkeypatch.setenv("TPU_LLM_DEVICE_QUARANTINE_FAILURES", "2")
        monkeypatch.setenv("TPU_LLM_DEVICE_COOLDOWN_S", "1.0")
        inj = FaultInjector()
        metrics = new_metrics_manager()
        rep = _fleet(params, inj, supervise=True, metrics=metrics)
        try:
            home = rep._device_keys[0]
            # exile every spare device: quarantine them with escalated
            # cooldowns so only the home device can come back first
            for d in jax.devices():
                k = device_key(d)
                if k in rep._device_keys:
                    continue
                for _ in range(6):  # trip + re-trips: cooldown 1 -> 8s
                    rep.health.record_failure(k, "step_fault")
            corpse = rep.engines[0]
            inj.arm("device_sick", label=home, count=1)  # only the 1st rebuild
            inj.arm("replica_kill", label="/r0")
            _wait(lambda: not corpse.alive(), 10, "replica 0 death")
            # home quarantined + no usable alternate -> PARKED, visibly
            _wait(
                lambda: rep.supervisor.parked_count() == 1, 30, "slot parked",
            )
            assert metrics.gauge_total("app_llm_replicas_parked") == 1.0
            assert rep.stats()["replicas_parked"] == 1
            snap = rep.supervisor.snapshot()
            assert snap["pending"][0]["parked"] is True
            assert "no usable device" in snap["pending"][0]["reason"]
            # health endpoint reports degraded while capacity is short
            from types import SimpleNamespace

            from gofr_tpu.config import new_mock_config
            from gofr_tpu.handler import _serving_status

            container = SimpleNamespace(
                config=new_mock_config({}), metrics_manager=metrics,
            )
            assert _serving_status(container) == "degraded"
            # cooldown elapses -> home in probation -> probe rebuild
            # passes the canary -> slot restored ON THE HOME DEVICE and
            # the device reintegrated (capacity back, gauges clear)
            _wait(
                lambda: rep.engines[0] is not corpse
                and rep.engines[0].alive(),
                60, "reintegration rebuild",
            )
            assert rep._current_keys[0] == home
            _wait(
                lambda: rep.health.state(home) == "healthy", 10,
                "home reintegrated",
            )
            assert rep.supervisor.parked_count() == 0
            assert metrics.gauge_total("app_llm_replicas_parked") == 0.0
            assert _serving_status(container) == "UP"
            toks = rep.engines[0].generate([5, 9, 2], max_new_tokens=4)
            assert toks == _reference_tokens(params, [5, 9, 2], 4)
            assert rep.stats()["replicas_alive"] == 2
        finally:
            inj.disarm()
            rep.close()

    def test_restart_max_attempts_marks_slot_failed(self, params, monkeypatch):
        monkeypatch.setenv("TPU_LLM_SUPERVISOR_INTERVAL_S", "0.02")
        monkeypatch.setenv("TPU_LLM_RESTART_BACKOFF_S", "0.02")
        monkeypatch.setenv("TPU_LLM_RESTART_MAX_ATTEMPTS", "2")
        # devices never quarantine here: this is the everything-is-sick
        # case (param corruption, driver gone) the attempt cap exists for
        monkeypatch.setenv("TPU_LLM_DEVICE_QUARANTINE_FAILURES", "100")
        inj = FaultInjector()
        metrics = new_metrics_manager()
        rep = _fleet(params, inj, supervise=True, metrics=metrics)
        try:
            inj.arm("device_sick", count=-1)  # EVERY device refuses builds
            inj.arm("replica_kill", label="/r0")
            _wait(lambda: not rep.engines[0].alive(), 10, "replica 0 death")
            _wait(
                lambda: rep.supervisor.failed_count() == 1, 30,
                "permanent failure",
            )
            assert rep.supervisor.restart_failures == 2
            time.sleep(0.3)  # several intervals: no further attempts
            assert rep.supervisor.restart_failures == 2, "kept retrying"
            snap = rep.supervisor.snapshot()
            assert snap["pending"][0]["failed"] is True
            assert "permanently failed after 2" in snap["pending"][0]["reason"]
            assert metrics.gauge_total("app_llm_replicas_failed") == 1.0
            assert rep.stats()["replicas_failed"] == 1
            # the survivor keeps serving
            toks = rep.generate([5, 9, 2], max_new_tokens=4)
            assert toks == _reference_tokens(params, [5, 9, 2], 4)
        finally:
            inj.disarm()
            rep.close()

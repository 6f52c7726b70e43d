"""Compile & device-program observability tests: instrument_jit compile
accounting under shape-bucket churn, analytic-FLOPs math against known
tiny-transformer values, MFU gauge emission on the CPU backend, the
profile capture concurrency guard (second capture -> 409), the
/.well-known/debug/compiles JSON shape, and the engine-teardown
regression (a closed engine must neither list its programs nor keep
exporting utilization gauges).

Capture tests force the PARKED (pure-Python fallback) path by breaking
jax.profiler.start_trace: the first real jax trace pays ~10 s of one-time
profiler init, which belongs in the CI smoke (scripts/smoke_profiling.py),
not in tier-1. Engines get unique kv_labels so the process-global
registry never crosses test boundaries."""

import json
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from gofr_tpu.config import new_mock_config
from gofr_tpu.llm import LLMEngine
from gofr_tpu.metrics import new_metrics_manager
from gofr_tpu.models import TransformerConfig, init_params
from gofr_tpu.profiling import (
    CompileRegistry,
    default_registry,
    instrument_jit,
    register_compile_metrics,
)
from gofr_tpu.profiling import mfu as mfu_mod
from gofr_tpu.profiling.capture import ProfileBusy, ProfilerCapture

CFG = TransformerConfig.tiny()


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture()
def parked_profiler(monkeypatch):
    """Force capture onto the pure-Python fallback path (no 10 s one-time
    jax profiler init in tier-1; the real trace runs in the CI smoke)."""

    def _refuse(*_a, **_k):
        raise RuntimeError("profiler disabled for test")

    monkeypatch.setattr(jax.profiler, "start_trace", _refuse)
    return _refuse


class TestInstrumentJit:
    def test_recompile_counting_under_shape_bucket_churn(self):
        """Each new abstract signature compiles once; repeats are
        trace-cache hits. The registry keeps one row per shape bucket."""
        reg = CompileRegistry()
        metrics = new_metrics_manager()
        calls = []
        f = instrument_jit(
            "churn", lambda x: (x * 2).sum(), model="m",
            registry=reg, metrics=metrics,
        )
        for n in (4, 8, 4, 8, 4, 16):
            calls.append(float(f(jnp.ones((n,)))))
        assert calls == [8.0, 16.0, 8.0, 16.0, 8.0, 32.0]
        snap = reg.snapshot()
        assert snap["totals"]["programs"] == 3  # one row per bucket
        assert snap["totals"]["compiles"] == 3
        assert snap["totals"]["cache_hits"] == 3
        by_shape = {tuple(e["arg_shapes"]): e for e in snap["programs"]}
        assert by_shape[("float32[4]",)]["hits"] == 2
        assert by_shape[("float32[16]",)]["hits"] == 0
        for e in snap["programs"]:
            assert e["program"] == "churn" and e["model"] == "m"
            assert e["compile_s"] > 0
        expo = metrics.render_prometheus()
        assert 'app_jax_compiles_total{model="m",program="churn"} 3' in expo
        assert 'app_jax_trace_cache_hits_total{model="m",program="churn"} 3' in expo
        assert "app_jax_compile_seconds_bucket" in expo

    def test_cost_analysis_and_donation(self):
        """cost_analysis FLOPs land in the entry; donated buffers flow
        through the AOT executable exactly as through jax.jit."""
        reg = CompileRegistry()
        f = instrument_jit(
            "donate", lambda a, b: a + b, registry=reg, donate_argnums=(0,),
        )
        out = f(jnp.ones((64,)), jnp.ones((64,)))
        out = f(out, jnp.ones((64,)))  # chained donation, cache hit
        assert float(out[0]) == 3.0
        e = reg.snapshot()["programs"][0]
        assert e["compiles"] == 1 and e["hits"] == 1
        assert e["flops"] and e["flops"] >= 64

    def test_trace_errors_propagate_like_jit(self):
        """A bad input batch raises the same error jax.jit would — and
        must not silently degrade the wrapper for later good calls."""
        reg = CompileRegistry()
        f = instrument_jit("bad", lambda a, b: a * b, registry=reg)
        with pytest.raises(Exception):
            f(jnp.ones((4,)), jnp.ones((8,)))
        assert float(f(jnp.ones((4,)), jnp.ones((4,)))[0]) == 1.0
        assert reg.snapshot()["programs"][0]["measured"] == "aot"

    def test_leaving_aot_dispatch_is_listed_not_silent(self):
        """An executable that rejects intact inputs drops the wrapper to
        plain jit for good — and the registry says so, with the error."""
        reg = CompileRegistry()
        f = instrument_jit("drift", lambda a: a + 1, registry=reg, model="m")
        x = jnp.ones((4,))
        f(x)
        assert reg.snapshot()["degraded"] == []

        def reject(*_a):
            raise ValueError("layout drift")

        for sig in list(f._compiled):
            f._compiled[sig] = reject
        assert float(f(x)[0]) == 2.0  # served through plain jit
        assert reg.snapshot()["degraded"] == [
            {"program": "drift", "model": "m", "reason": "ValueError: layout drift"}
        ]
        assert reg.snapshot(model="other")["degraded"] == []

    def test_static_argnums_compile_per_value(self):
        """Static args are compile-time constants: distinct values must
        compile distinct executables (never collide on one signature),
        and the AOT call must strip them like jax's own Compiled does."""
        reg = CompileRegistry()
        f = instrument_jit(
            "static", lambda x, n: x[:n].sum(), registry=reg,
            static_argnums=(1,),
        )
        import jax.numpy as jnp

        assert float(f(jnp.arange(8.0), 4)) == 6.0
        assert float(f(jnp.arange(8.0), 8)) == 28.0
        assert float(f(jnp.arange(8.0), 4)) == 6.0  # cache hit
        t = reg.snapshot()["totals"]
        assert t["compiles"] == 2 and t["cache_hits"] == 1, t

    def test_pytree_args_collapse_in_registry_rows(self):
        reg = CompileRegistry()
        f = instrument_jit("tree", lambda p, x: p["w"] @ x, registry=reg)
        f({"w": jnp.ones((4, 4))}, jnp.ones((4,)))
        shapes = reg.snapshot()["programs"][0]["arg_shapes"]
        assert shapes == ["pytree[1 leaves]", "float32[4]"]

    def test_arg0_memo_drops_ref_when_caller_rebinds(self):
        """Train steps rebind params every call; the signature memo must
        stop pinning whole dead parameter trees after the identity
        stops hitting (it would hold a full stale generation in HBM)."""
        reg = CompileRegistry()
        f = instrument_jit("rebind", lambda p, x: p["w"].sum() + x, registry=reg)
        x = jnp.float32(0.0)
        p = {"w": jnp.ones((4,))}
        f(p, x)
        f(p, x)
        assert f._arg0_memo is not None and f._arg0_memo[0] is p  # stable id: memo hits
        for _ in range(3):  # churning identity, same shapes
            p = {"w": p["w"] + 1}
            f(p, x)
        assert f._arg0_memo is None  # no stale tree pinned
        assert reg.snapshot()["totals"]["compiles"] == 1  # still one executable


class TestAnalyticFlops:
    def test_tiny_transformer_costs_exact(self):
        """Hand-computed values for TransformerConfig.tiny(): d=64, L=2,
        H=4, Hkv=2, hd=16, dff=128, vocab=512, f32."""
        c = mfu_mod.model_costs(CFG)
        layer = (64 * (4 + 2 * 2) * 16 + 4 * 16 * 64 + 3 * 64 * 128) * 2
        embed = 512 * 64
        assert c.layer_params == layer == 73728
        assert c.embed_params == embed == 32768
        assert c.params == layer + embed
        assert c.matmul_flops_per_token == 2 * (layer + embed)
        assert c.attn_flops_per_token_per_ctx == 4 * 2 * 4 * 16 == 512
        # KV bytes per attended position: 2 (k+v) * L * Hkv * hd * 4 (f32)
        assert c.kv_bytes_per_ctx_token == 2 * 2 * 2 * 16 * 4
        assert c.params_bytes == (layer + embed) * 4
        assert mfu_mod.model_costs(CFG, quantized=True).params_bytes == layer + embed

    def test_decode_and_prefill_flops(self):
        c = mfu_mod.model_costs(CFG)
        assert mfu_mod.decode_flops(c, 3, 30) == (
            3 * c.matmul_flops_per_token + 30 * c.attn_flops_per_token_per_ctx
        )
        # one 8-token prompt: causal attention attends 8*9/2 positions,
        # the unembed matmul fires once (last position only)
        got = mfu_mod.prefill_flops(c, [8])
        assert got == (
            2 * 8 * c.layer_params + 2 * c.embed_params
            + c.attn_flops_per_token_per_ctx * 36
        )
        # sliding window caps the attended span EXACTLY: the first w
        # tokens attend causally, every later token attends w positions
        cw = mfu_mod.model_costs(TransformerConfig.tiny_mistral())
        assert cw.sliding_window == 8
        assert mfu_mod.prefill_flops(cw, [32]) == (
            2 * 32 * cw.layer_params + 2 * cw.embed_params
            + cw.attn_flops_per_token_per_ctx * (8 * 9 / 2 + (32 - 8) * 8)
        )
        # prompts shorter than the window are the plain causal triangle
        assert mfu_mod.prefill_flops(cw, [4]) == (
            2 * 4 * cw.layer_params + 2 * cw.embed_params
            + cw.attn_flops_per_token_per_ctx * 10
        )

    def test_device_peaks_and_env_override(self, monkeypatch):
        assert mfu_mod.device_peak_flops("tpu", "TPU v5 lite") == 197e12
        assert mfu_mod.device_hbm_bandwidth("tpu", "TPU v5 lite") == 8.2e11
        assert mfu_mod.device_peak_flops("tpu", "TPU v5p") == 459e12
        assert mfu_mod.device_peak_flops("cpu", "cpu") == 1e12  # placeholder
        monkeypatch.setenv("TPU_PEAK_FLOPS", "5e12")
        assert mfu_mod.device_peak_flops("cpu", "cpu") == 5e12

    def test_roofline_classification(self):
        # decode at v5e: tiny FLOPs over the whole weight stream -> memory
        assert mfu_mod.classify_bound(
            mfu_mod.roofline_ratio(1e9, 5e9, 197e12, 8.2e11)
        ) == "memory"
        assert mfu_mod.classify_bound(
            mfu_mod.roofline_ratio(1e12, 1e6, 197e12, 8.2e11)
        ) == "compute"
        assert mfu_mod.classify_bound(0.0) == "unknown"


class TestEngineMFU:
    @pytest.fixture(scope="class")
    def engine(self, params):
        metrics = new_metrics_manager()
        eng = LLMEngine(
            CFG, params, slots=2, max_seq_len=64, prefill_buckets=(8,),
            metrics=metrics, kv_label="mfu-test",
        )
        yield eng, metrics
        eng.close()

    def test_mfu_gauges_emitted_on_cpu_backend(self, engine):
        eng, metrics = engine
        assert len(eng.generate([5, 9, 2], max_new_tokens=6)) == 6
        expo = metrics.render_prometheus()
        for frag in (
            'app_llm_mfu{model="mfu-test",phase="decode"}',
            'app_llm_mfu{model="mfu-test",phase="prefill"}',
            'app_llm_tokens_per_second_per_chip{model="mfu-test"}',
            'app_llm_roofline_ratio{model="mfu-test",phase="decode"}',
        ):
            assert frag in expo, frag
        # gauge values are live utilizations: positive, MFU sane (<1 on
        # the CPU placeholder peak for a tiny model)
        for line in expo.splitlines():
            if line.startswith('app_llm_mfu{model="mfu-test"'):
                assert 0.0 < float(line.rsplit(" ", 1)[1]) < 1.0, line

    def test_stats_mfu_block_and_warmup(self, engine):
        eng, _ = engine
        eng.generate([5, 9], max_new_tokens=4)
        st = eng.stats()
        m = st["mfu"]
        assert m["chips"] == 1 and m["peak_flops_per_chip"] > 0
        assert m["params"] == mfu_mod.model_costs(CFG).params
        assert m["decode"]["count"] >= 1 and m["decode"]["p50"] > 0
        assert m["prefill"]["count"] >= 1
        assert m["tokens_per_second_per_chip"]["p50"] > 0
        assert m["roofline"]["bound"] in ("memory", "compute")
        # warmed engine recorded its cold-start bill
        assert st["warmup_s"] and st["warmup_s"] > 0
        snap = default_registry().snapshot(model="mfu-test")
        assert snap["warmup"]["mfu-test"]["seconds"] == round(st["warmup_s"], 3)

    def test_debug_state_lists_compiled_programs(self, engine):
        eng, _ = engine
        dbg = eng.debug_state()
        programs = {e["program"] for e in dbg["compiles"]}
        # chunked scheduler: the unified step family replaces the
        # monolithic llm.prefill programs in the warmed set
        assert {"llm.insert_many", "llm.admit_update"} <= programs
        assert any(p.startswith("llm.step_p") for p in programs)
        assert any(p.startswith("llm.decode_chunk") for p in programs)
        for e in dbg["compiles"]:
            assert e["model"] == "mfu-test" and e["compile_s"] >= 0
        assert dbg["mfu"]["decode"]["count"] >= 1

    def test_prefix_hit_wave_claims_no_prefill_mfu(self, params):
        """A prefix-cache-hit wave dispatches no device prefill — it must
        not inflate the prefill MFU window."""
        eng = LLMEngine(
            CFG, params, slots=2, max_seq_len=64, prefill_buckets=(8,),
            warmup=False, prefix_cache_mb=8.0, kv_label="mfu-hit-test",
        )
        try:
            prompt = [5, 9, 2]
            eng.generate(prompt, max_new_tokens=2)
            n_after_miss = eng._mfu_windows["prefill"].summary()["count"]
            eng.generate(prompt, max_new_tokens=2)  # prefix hit
            # layout-agnostic exact-hit counter (paged radix / PrefixCache)
            assert eng.stats()["kvcache"]["prefix"]["hits"] >= 1
            assert eng._mfu_windows["prefill"].summary()["count"] == n_after_miss
        finally:
            eng.close()


class TestTeardownRegression:
    def test_close_unregisters_registry_and_zeros_gauges(self, params):
        """The dead-engine-exporting bug class PR 2 fixed for slot gauges,
        applied to the new surfaces: after close(), the registry lists
        none of the engine's programs and the utilization gauges read 0."""
        metrics = new_metrics_manager()
        eng = LLMEngine(
            CFG, params, slots=2, max_seq_len=64, prefill_buckets=(8,),
            metrics=metrics, warmup=False, kv_label="teardown-test",
        )
        eng.generate([5, 9, 2], max_new_tokens=4)
        assert default_registry().snapshot(model="teardown-test")["programs"]
        expo = metrics.render_prometheus()
        assert 'app_llm_mfu{model="teardown-test",phase="decode"}' in expo
        eng.close()
        assert default_registry().snapshot(model="teardown-test")["programs"] == []
        for line in metrics.render_prometheus().splitlines():
            if (
                line.startswith(("app_llm_mfu{", "app_llm_roofline_ratio{",
                                 "app_llm_tokens_per_second_per_chip{"))
                and 'model="teardown-test"' in line
            ):
                assert line.endswith(" 0"), line


class TestCapture:
    def test_concurrency_guard_second_capture_409(self, parked_profiler, tmp_path):
        cap = ProfilerCapture(base_dir=str(tmp_path))
        results, errors = [], []

        def long_capture():
            results.append(cap.capture(1.0))

        t = threading.Thread(target=long_capture)
        t.start()
        time.sleep(0.2)
        with pytest.raises(ProfileBusy) as exc:
            cap.capture(0.2)
        assert exc.value.status_code == 409
        t.join()
        assert not errors and results[0]["mode"] == "fallback"
        # the guard releases: a follow-up capture succeeds
        assert cap.capture(0.1)["mode"] == "fallback"

    def test_parked_capture_archives_samples_and_reason(self, parked_profiler, tmp_path):
        cap = ProfilerCapture(base_dir=str(tmp_path))
        res = cap.capture(0.25, sample_fn=lambda: {"active": 1})
        assert res["mode"] == "fallback"
        assert "profiler disabled for test" in res["parked"]
        assert res["archive"][:2] == b"PK"
        assert "capture.json" in res["files"]
        assert "engine_samples.json" in res["files"]
        assert res["samples"] >= 1

    def test_non_finite_seconds_rejected_before_lock(self, tmp_path):
        """NaN slips through min/max clamps (comparisons all False) and
        would spin the window forever with the busy lock held."""
        cap = ProfilerCapture(base_dir=str(tmp_path))
        for bad in (float("nan"), float("inf"), float("-inf")):
            with pytest.raises(ValueError):
                cap.capture(bad)
        assert cap._busy.acquire(blocking=False)  # lock never leaked
        cap._busy.release()

    def test_until_exception_still_stops_trace(self, monkeypatch, tmp_path):
        """A raising until() (caller code) must not leak the process-global
        profiler in the started state — that would park every later
        capture until restart."""
        calls = {"start": 0, "stop": 0}
        monkeypatch.setattr(
            jax.profiler, "start_trace",
            lambda *_a, **_k: calls.__setitem__("start", calls["start"] + 1),
        )
        monkeypatch.setattr(
            jax.profiler, "stop_trace",
            lambda: calls.__setitem__("stop", calls["stop"] + 1),
        )
        cap = ProfilerCapture(base_dir=str(tmp_path))

        def boom():
            raise RuntimeError("until boom")

        with pytest.raises(RuntimeError, match="until boom"):
            cap.capture(5.0, until=boom)
        assert calls == {"start": 1, "stop": 1}
        # guard released AND profiler stopped: the next capture works
        assert cap.capture(0.1)["mode"] == "jax"
        assert calls == {"start": 2, "stop": 2}

    def test_until_condition_ends_capture_early(self, parked_profiler, tmp_path):
        cap = ProfilerCapture(base_dir=str(tmp_path))
        t0 = time.perf_counter()
        res = cap.capture(10.0, until=lambda: True)
        assert time.perf_counter() - t0 < 5.0
        assert res["seconds"] < 1.0


class TestEndpoints:
    @pytest.fixture(scope="class")
    def served(self, params):
        from gofr_tpu import App

        app = App(config=new_mock_config({
            "APP_NAME": "prof", "HTTP_PORT": "0", "METRICS_PORT": "0",
            "LOG_LEVEL": "ERROR", "TPU_TELEMETRY_INTERVAL_S": "0",
            "HEALTH_DEGRADED_QUEUE_DEPTH": "4",
            "HEALTH_DEGRADED_ADMISSION_BACKLOG": "50",
        }))
        app.container.tpu().register_llm(
            "tinyprof", CFG, params, slots=2, max_seq_len=64,
            prefill_buckets=(8,), warmup=False,
        )
        app.run_in_background()
        app.container.tpu().llm("tinyprof").generate([5, 9, 2], max_new_tokens=2)
        yield app, f"http://127.0.0.1:{app.http_server.port}"
        app.shutdown()

    def test_debug_compiles_json_shape(self, served):
        _, base = served
        with urllib.request.urlopen(f"{base}/.well-known/debug/compiles", timeout=10) as r:
            body = json.loads(r.read())["data"]
        assert set(body) == {
            "programs", "totals", "backend_events", "warmup", "degraded",
        }
        # the registry is process-wide: only this engine's rows are ours
        assert [d for d in body["degraded"] if d["model"] == "tinyprof"] == []
        mine = [e for e in body["programs"] if e["model"] == "tinyprof"]
        # chunked scheduler: prompts run through the unified step programs
        assert any(
            e["program"].startswith("llm.step_p") for e in mine
        ), {e["program"] for e in mine}
        for e in mine:
            for key in ("program", "model", "arg_shapes", "compiles", "hits",
                        "compile_s", "trace_s", "backend", "measured", "age_s"):
                assert key in e, key
            assert e["compiles"] >= 1 and e["arg_shapes"]
        assert body["totals"]["compiles"] >= len(mine)
        # jax.monitoring phase aggregates rode along
        assert any("compile" in k for k in body["backend_events"])

    def test_profile_endpoint_parks_cleanly_and_guards(self, served, parked_profiler):
        _, base = served
        req = urllib.request.Request(
            f"{base}/.well-known/debug/profile?seconds=0.2&download=0",
            method="POST",
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            meta = json.loads(r.read())["data"]
        assert meta["mode"] == "fallback" and meta["parked"]
        assert meta["samples"] >= 1  # engine stats sampled during the window
        assert "engine_samples.json" in meta["files"]

        # archive (zip) response by default
        req = urllib.request.Request(
            f"{base}/.well-known/debug/profile?seconds=0.2", method="POST"
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            data = r.read()
            assert r.headers["Content-Type"] == "application/zip"
        assert data[:2] == b"PK"

        # second capture while one runs -> 409 through the responder
        def hold():
            rq = urllib.request.Request(
                f"{base}/.well-known/debug/profile?seconds=2", method="POST"
            )
            urllib.request.urlopen(rq, timeout=30).read()

        t = threading.Thread(target=hold)
        t.start()
        time.sleep(0.5)
        rq = urllib.request.Request(
            f"{base}/.well-known/debug/profile?seconds=0.2", method="POST"
        )
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(rq, timeout=30)
        assert exc.value.code == 409
        t.join()

    def test_health_degraded_on_queue_depth(self, served):
        app, base = served

        def status():
            with urllib.request.urlopen(f"{base}/.well-known/health", timeout=10) as r:
                return json.loads(r.read())["data"]["status"]

        assert status() == "UP"  # idle engine under both thresholds
        # push the PR-2 gauge over the configured threshold (4) under a
        # label the live engine does not refresh every scheduler pass
        # (gauge_total sums across label sets, like a real replica fleet)
        app.container.metrics.set_gauge(
            "app_llm_queue_depth", 9.0, model="other-replica"
        )
        try:
            assert status() == "degraded"
        finally:
            app.container.metrics.set_gauge(
                "app_llm_queue_depth", 0.0, model="other-replica"
            )
        assert status() == "UP"

    def test_health_thresholds_unset_stays_up(self, params):
        """Legacy behavior: no thresholds configured -> always UP, even
        with a deep queue gauge."""
        from gofr_tpu import App

        app = App(config=new_mock_config({
            "APP_NAME": "nothr", "HTTP_PORT": "0", "METRICS_PORT": "0",
            "LOG_LEVEL": "ERROR",
        }))
        app.container.metrics.new_gauge("app_llm_queue_depth", "t")
        app.container.metrics.set_gauge("app_llm_queue_depth", 999.0, model="x")
        app.run_in_background()
        try:
            base = f"http://127.0.0.1:{app.http_server.port}"
            with urllib.request.urlopen(f"{base}/.well-known/health", timeout=10) as r:
                body = json.loads(r.read())["data"]
            assert body["status"] == "UP"
            assert body["app"]["status"] == "UP"
        finally:
            app.shutdown()


class TestCLI:
    def test_profile_subcommand_parks_and_writes_archive(
        self, parked_profiler, tmp_path, capsys,
    ):
        from gofr_tpu.cmd import CMDApp

        out_zip = tmp_path / "prof.zip"
        app = CMDApp(config=new_mock_config({"LOG_LEVEL": "ERROR"}))
        rc = app.run([
            "profile", "-seconds=0.2", f"-dir={tmp_path}", f"-out={out_zip}",
        ])
        assert rc == 0
        printed = capsys.readouterr().out
        assert "mode=fallback" in printed and "parked" in printed
        assert out_zip.read_bytes()[:2] == b"PK"

    def test_profile_listed_in_help(self, capsys):
        from gofr_tpu.cmd import CMDApp

        app = CMDApp(config=new_mock_config({"LOG_LEVEL": "ERROR"}))
        assert app.run([]) == 0
        assert "profile" in capsys.readouterr().out

    def test_builtin_never_hijacks_user_subcommands(self, capsys):
        """User routes dispatch before the builtin, and the anchored
        pattern must not swallow `profile-export`-style names."""
        from gofr_tpu.cmd import CMDApp

        app = CMDApp(config=new_mock_config({"LOG_LEVEL": "ERROR"}))
        app.sub_command("profile-export", lambda ctx: "user-export")
        app.sub_command("profile", lambda ctx: "user-profile")
        assert app.run(["profile-export"]) == 0
        assert "user-export" in capsys.readouterr().out
        assert app.run(["profile"]) == 0
        assert "user-profile" in capsys.readouterr().out


def test_register_compile_metrics_idempotent():
    m = new_metrics_manager()
    register_compile_metrics(m)
    register_compile_metrics(m)  # second call must not warn/replace
    assert m.has("app_jax_compile_seconds")
    assert m.has("app_jax_compiles_total")
    assert m.has("app_jax_trace_cache_hits_total")

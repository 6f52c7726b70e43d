"""The step timeline: one finished record per dispatched program in the
engine's ring (`stats()["step_log"]`, `debug_state()["steps"]`) and the
engine threads' spans in a captured profile, on a tiny CPU engine.

What each is for: docs/advanced-guide/profiling.md, "The step timeline"."""

import glob
import os
import threading
import time

import jax
import pytest

import gofr_tpu
import gofr_tpu.llm as llm_mod
from gofr_tpu.llm import STEP_FIELDS, STEP_LOG_LEN, GenRequest, LLMEngine
from gofr_tpu.models import TransformerConfig, init_params
from gofr_tpu.profiling import engine_span
from gofr_tpu.resilience import Heartbeat

CFG = TransformerConfig.tiny()
# chunked fused steps over the paged pool / the slab, speculative verify, and
# the wave scheduler whose admission dispatches the prefill programs
ENGINES = {
    "paged": dict(kv_paged=True, step_token_budget=32, prefill_buckets=(16,)),
    "slab": dict(kv_paged=False, step_token_budget=32, prefill_buckets=(16,)),
    "verify": dict(kv_paged=True, prefill_buckets=(16,), decode_chunk=4,
                   speculative=True, spec_draft=4),
    "wave": dict(kv_paged=False, step_token_budget=0, prefill_buckets=(16, 64)),
}
KINDS = {"paged": {"step", "chunk"}, "slab": {"step", "chunk"},
         "verify": {"step", "chunk", "verify"}, "wave": {"prefill", "chunk"}}
MUST = {"paged": {"step"}, "slab": {"step"}, "verify": {"verify"}, "wave": {"prefill", "chunk"}}


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def serve(engine, prompts, max_new):
    reqs = [engine.submit(GenRequest(p, max_new_tokens=max_new, temperature=0.0, eos_token=-1))
            for p in prompts]
    outs = [r.tokens(timeout=120) for r in reqs]
    deadline = time.time() + 10
    while engine.stats()["inflight_chunks"] and time.time() < deadline:
        time.sleep(0.01)  # tail chunks of already-finished requests
    time.sleep(0.05)
    return reqs, outs


@pytest.fixture(scope="module", params=sorted(ENGINES))
def served(request, params):
    """One engine of each scheduler, three requests served, its log read."""
    name = request.param
    engine = LLMEngine(CFG, params, slots=4, max_seq_len=128, warmup=False,
                       kv_label=f"timeline-{name}", **ENGINES[name])
    try:
        if name == "verify":  # a repetitive prompt, so that the drafter proposes
            prompts = [[7, 8, 9, 10] * 8, [3, 4] * 12, [5] * 20]
        else:
            prompts = [list(range(1, 40)), list(range(5, 25)), list(range(9, 70))]
        _reqs, outs = serve(engine, prompts, 20)
        log = engine.stats()["step_log"]
        yield name, engine, prompts, outs, [dict(zip(log["fields"], r)) for r in log["records"]]
    finally:
        engine.close()


def test_fields_are_the_declared_ones(served):
    name, engine, _p, _o, recs = served
    assert engine.stats()["step_log"]["fields"] == STEP_FIELDS
    assert recs and all(set(r) == set(STEP_FIELDS) for r in recs)
    assert MUST[name] <= {r["kind"] for r in recs} <= KINDS[name]
    assert all(r["program"].startswith("llm.") for r in recs if r["kind"] != "prefill" or r["rows"])


def test_seq_is_monotone_and_gap_free(served):
    _n, _e, _p, _o, recs = served
    seqs = sorted(r["seq"] for r in recs)
    assert seqs == list(range(seqs[0], seqs[0] + len(seqs)))  # every dispatched program was finished
    by_seq = sorted(recs, key=lambda r: r["seq"])
    assert all(a["t_dispatch"] <= b["t_dispatch"] for a, b in zip(by_seq, by_seq[1:]))
    # the ring is in the order the collector finished them
    assert all(a["t_emitted"] <= b["t_emitted"] for a, b in zip(recs, recs[1:]))


def test_stamps_are_ordered(served):
    _n, _e, _p, _o, recs = served
    for r in recs:
        assert (r["t_dispatch"] <= r["t_dispatched"] <= r["t_fetch"] <= r["t_fetched"]
                <= r["t_emitted"] <= time.perf_counter()), r


def test_rows_sum_to_the_prompts(served):
    """No prefix hit and no preemption: every prompt token rode exactly one row."""
    _n, _e, prompts, _o, recs = served
    rows = [row for r in recs for row in r["rows"]]
    assert sum(n for _start, n, _shape in rows) == sum(len(p) for p in prompts)
    assert all(0 < n <= shape for _start, n, shape in rows)
    # each prompt's rows tile it: starts 0, n0, n0+n1, ... up to its length
    ends = sorted(start + n for start, n, _shape in rows)
    assert all(len(p) in ends for p in prompts)


def test_emitted_sums_to_what_consumers_received(served):
    _n, _e, _p, outs, recs = served
    assert sum(r["emitted"] for r in recs) == sum(len(o) for o in outs) == 60
    for r in recs:
        decoded = sum(n for _ctx, n in r["decode_ctx"])
        assert decoded <= r["emitted"] and len(r["decode_ctx"]) <= max(r["lanes"], len(r["decode_ctx"]))
        assert all(n <= max(r["k"], 1) for _ctx, n in r["decode_ctx"])


def test_decode_contexts_continue_each_request(served):
    """A lane's context at a program's start is its prompt plus what it had
    emitted: over a request's programs the contexts tile prompt+1 .. prompt+20."""
    name, _e, prompts, _o, recs = served
    seen = sorted(c for r in recs for ctx, n in r["decode_ctx"] for c in range(ctx + 1, ctx + n + 1))
    first_tokens = len(prompts)  # a request's first token comes out of its prompt's last row
    assert len(seen) == 60 - first_tokens
    if name != "verify":  # (a verify emits its bonus token from the cursor's own logits)
        want = sorted(c for p in prompts for c in range(len(p) + 2, len(p) + 21))
        assert seen == want


def test_depth_counts_the_pipeline(served):
    _n, engine, _p, _o, recs = served
    assert all(0 <= r["depth"] <= engine.lookahead for r in recs)
    assert recs[0]["depth"] == 0 or min(r["depth"] for r in recs) == 0


def test_stats_copies_references_not_records(served):
    """The harness's sampler calls stats() once a second: no work per record."""
    _n, engine, _p, _o, _r = served
    a, b = engine.stats()["step_log"]["records"], engine.stats()["step_log"]["records"]
    assert isinstance(a, tuple) and all(isinstance(r, tuple) for r in a)
    assert len(a) == len(b) and all(x is y for x, y in zip(a, b))


def test_debug_state_shows_the_newest_as_dicts(served):
    _n, engine, _p, _o, recs = served
    steps = engine.debug_state()["steps"]
    assert 0 < len(steps) <= 32 and steps == recs[-len(steps):]
    import json

    json.dumps(steps)  # an operator's endpoint serves it


def test_the_ring_is_bounded(params, monkeypatch):
    assert STEP_LOG_LEN >= 2 * 50 / 0.025  # two 50 s windows at 25 ms a program
    monkeypatch.setattr(llm_mod, "STEP_LOG_LEN", 4)
    engine = LLMEngine(CFG, params, slots=2, max_seq_len=128, warmup=False,
                       kv_label="timeline-ring", step_token_budget=32, prefill_buckets=(16,))
    try:
        serve(engine, [list(range(1, 30))], 40)
        log = engine.stats()["step_log"]["records"]
        assert len(log) == 4
        seqs = [r[STEP_FIELDS.index("seq")] for r in log]
        assert max(seqs) > 4  # the oldest were dropped, the newest kept
    finally:
        engine.close()


def test_the_log_is_reachable_through_the_handle(params):
    """As the harness reaches it: app.container.tpu().register_llm(...).stats()."""
    os.environ.setdefault("TPU_TELEMETRY_INTERVAL_S", "0")
    app = gofr_tpu.new()
    handle = app.container.tpu().register_llm(
        "timeline", CFG, params, slots=2, max_seq_len=128, warmup=False, prefill_buckets=(16,))
    try:
        req = handle.submit(GenRequest(list(range(1, 20)), max_new_tokens=8, temperature=0.0, eos_token=-1))
        assert len(list(req.stream(timeout=120))) == 8
        time.sleep(0.1)
        log = handle.stats()["step_log"]
        assert log["fields"] == STEP_FIELDS
        assert sum(r[STEP_FIELDS.index("emitted")] for r in log["records"]) == 8
    finally:
        app.container.tpu().close()


def test_engine_span_is_one_with_for_span_and_heartbeat():
    hb = Heartbeat()
    with engine_span("dispatch.call", hb, kind="chunk") as span:
        assert hb.stalled()[0] == "dispatch.call:chunk"
        span.set(seq=3)
    assert hb.stalled() == (None, 0.0)
    with pytest.raises(ValueError):
        with engine_span("collect.fetch", hb, seq=1):
            assert hb.stalled()[0] == "collect.fetch"
            raise ValueError("the beat ends with the span")
    assert hb.stalled() == (None, 0.0)
    with engine_span("sched.wait"):  # no heartbeat: a span alone
        pass


SPANS = {
    "llm-engine-sched": {"sched.housekeep", "sched.admit", "sched.plan", "sched.dispatch",
                         "sched.wait", "dispatch.inputs", "dispatch.call"},
    "llm-engine-collect": {"collect.wait", "collect.fetch", "collect.emit"},
}


def test_a_captured_profile_holds_the_spans_on_the_named_threads(params, tmp_path):
    """At the level the benchmark traces with (host_tracer_level 1): every span
    name on the line of its thread, `seq` readable, the program's own name
    around the executable, and dispatch spans that agree with the records."""
    engine = LLMEngine(CFG, params, slots=4, max_seq_len=128, warmup=False,
                       kv_label="timeline-trace", kv_paged=True, step_token_budget=32,
                       prefill_buckets=(16,))
    # A capture holds the spans that BEGIN inside it. An idle scheduler waits
    # for a request inside sched.admit (50 ms a pass), so a capture that
    # starts meanwhile lacks that span, and a request that arrives before the
    # pass ends is admitted where the capture cannot see it (`admitted` then
    # sums to 0 or 1; one run in five here before this wait). The requests go
    # in once a pass has begun after the capture did.
    passed, admit = threading.Event(), engine._admit

    def _admit():
        passed.set()
        return admit()

    engine._admit = _admit
    try:
        serve(engine, [list(range(1, 20))], 8)  # compiled before the capture
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            passed.clear()
            assert passed.wait(10)
            serve(engine, [list(range(1, 40)), list(range(3, 30))], 24)
        finally:
            jax.profiler.stop_trace()
        log = engine.stats()["step_log"]
        records = {r[0]: dict(zip(log["fields"], r)) for r in log["records"]}
    finally:
        engine.close()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    lines = {}
    for plane in data.planes:
        for line in plane.lines:
            for thread in SPANS:
                if len(line.name) >= 15 and thread.startswith(line.name):  # Linux keeps 15 bytes
                    lines.setdefault(thread, []).extend(line.events)
    assert set(lines) == set(SPANS)
    for thread, want in SPANS.items():
        assert want <= {ev.name for ev in lines[thread]}, thread
    dispatched = [dict(ev.stats) for ev in lines["llm-engine-sched"] if ev.name == "sched.dispatch"]
    with_seq = [s for s in dispatched if "seq" in s]
    assert with_seq
    for s in with_seq:
        rec = records[s["seq"]]
        assert (s["kind"], s["program"]) == (rec["kind"], rec["program"])
    fetched = {dict(ev.stats)["seq"] for ev in lines["llm-engine-collect"] if ev.name == "collect.fetch"}
    emitted = {dict(ev.stats)["seq"] for ev in lines["llm-engine-collect"] if ev.name == "collect.emit"}
    assert fetched and fetched == emitted and {s["seq"] for s in with_seq} <= fetched | {max(fetched) + 1}
    admitted = [dict(ev.stats)["admitted"] for ev in lines["llm-engine-sched"] if ev.name == "sched.admit"]
    assert sum(admitted) == 2
    programs = {rec["program"] for rec in records.values()}
    assert programs & {ev.name for ev in lines["llm-engine-sched"]}  # InstrumentedJit's span

"""The request timeline: one record per request at its first token in the
engine's ring (`stats()["request_log"]`, `debug_state()["requests"]`), its
prompt rows' programs named by the `seq` of the step records, on tiny CPU
engines of every path that dispatches prompt rows.

What each field is and where it is stamped: docs/advanced-guide/profiling.md,
"The request timeline"."""

import json
import math
import time

import jax
import pytest

import gofr_tpu.llm as llm_mod
from gofr_tpu import tracing as gt
from gofr_tpu.config import new_mock_config
from gofr_tpu.llm import REQUEST_FIELDS, REQUEST_LOG_LEN, GenRequest, LLMEngine
from gofr_tpu.models import TransformerConfig, init_params

CHUNK = 16
_KW = dict(slots=4, max_seq_len=160, prefill_chunk=CHUNK, prefill_buckets=(CHUNK,),
           step_token_budget=2 * CHUNK, warmup=False)
# the paths that dispatch prompt rows: fused steps over the paged pool and over
# the contiguous rolling ring, a stack of linear and full layers, and the wave
# scheduler whose admission prefills a whole prompt in one program
ENGINES = {
    "paged": ("tiny_qwen2", dict(_KW, kv_paged=True)),
    "ring": ("tiny_mistral", dict(_KW)),
    "linear": ("tiny_olmo_hybrid", dict(_KW)),
    "wave": ("tiny", dict(_KW, kv_paged=False, step_token_budget=0, prefill_buckets=(16, 64))),
}
PROMPTS = [list(range(1, 40)), list(range(5, 25)), list(range(9, 70))]

_built: dict = {}


def model(preset):
    if preset not in _built:
        cfg = getattr(TransformerConfig, preset)()
        _built[preset] = cfg, init_params(jax.random.PRNGKey(0), cfg)
    return _built[preset]


def engine_of(name, **kw) -> LLMEngine:
    preset, base = ENGINES[name]
    cfg, params = model(preset)
    return LLMEngine(cfg, params, **{**base, "kv_label": f"reqtl-{name}", **kw})


def settle(engine) -> None:
    """Every dispatched program's record closed."""
    for _ in range(1000):
        with engine._lock:
            if not engine._inflight and engine._processing is None:
                return
        time.sleep(0.01)


def serve(engine, prompts, max_new):
    reqs = [engine.submit(GenRequest(p, max_new_tokens=max_new, temperature=0.0, eos_token=-1))
            for p in prompts]
    for r in reqs:
        assert len(r.tokens(timeout=120)) == max_new
    settle(engine)
    return reqs


def records(engine) -> dict:
    log = engine.stats()["request_log"]
    assert log["fields"] == REQUEST_FIELDS
    return {rec[0]: dict(zip(REQUEST_FIELDS, rec)) for rec in log["records"]}


def steps(engine) -> dict:
    log = engine.stats()["step_log"]
    return {rec[0]: dict(zip(log["fields"], rec)) for rec in log["records"]}


def pieces(rec, by_seq):
    """(first_row_wait, prompt_rows, first_token_tail) from a request record
    joined to the step records by seq, as the benchmark's readers join them."""
    a = by_seq[rec["seq_first_row"]]["t_dispatch"]
    b = by_seq[rec["seq_last_row"]]["t_dispatch"]
    return a - rec["t_submit"], b - a, rec["t_first_token"] - b


@pytest.fixture(scope="module", params=sorted(ENGINES))
def served(request):
    name = request.param
    engine = engine_of(name)
    try:
        reqs = serve(engine, PROMPTS, 12)
        yield name, engine, reqs, records(engine), steps(engine)
    finally:
        engine.close()


def test_one_record_a_request_with_the_declared_fields(served):
    _n, engine, reqs, recs, _s = served
    assert set(recs) == {r.id for r in reqs}
    for r in reqs:
        rec = recs[r.id]
        assert (rec["prompt"], rec["cached"]) == (len(r.prompt_tokens), 0)
        assert (rec["t_submit"], rec["t_admit"], rec["t_first_token"]) == (
            r.submitted_at, r.admitted_at, r.first_token_at)
        assert (rec["seq_first_row"], rec["seq_last_row"], rec["rows"], rec["row_steps"]) == (
            r.seq_first_row, r.seq_last_row, r.rows, r.row_steps)
    newest = engine.debug_state()["requests"]
    assert newest == [recs[i] for i in sorted(recs, key=lambda i: recs[i]["t_first_token"])]
    json.dumps(newest)  # an operator's endpoint serves it


def test_rows_count_the_prompts_chunks(served):
    name, _e, reqs, recs, _s = served
    for r in reqs:
        rec = recs[r.id]
        chunk = 64 if name == "wave" else CHUNK  # the wave's bucket holds each prompt whole
        assert rec["rows"] == math.ceil((rec["prompt"] - rec["cached"]) / chunk)
        assert rec["row_steps"] == rec["rows"]  # one row of a request a program


def test_seqs_name_the_programs_that_carried_the_rows(served):
    name, _e, reqs, recs, by_seq = served
    kind = "prefill" if name == "wave" else "step"
    for r in reqs:
        rec = recs[r.id]
        first, last = by_seq[rec["seq_first_row"]], by_seq[rec["seq_last_row"]]
        assert rec["seq_first_row"] <= rec["seq_last_row"]
        assert first["kind"] == last["kind"] == kind
        # the first row starts at the cursor the cache left; the last row ends the prompt
        assert any(start == rec["cached"] for start, _n, _shape in first["rows"])
        assert any(start + n == rec["prompt"] for start, n, _shape in last["rows"])


def test_stamps_are_ordered_and_the_pieces_sum_to_ttft(served):
    _n, _e, reqs, recs, by_seq = served
    for r in reqs:
        rec = recs[r.id]
        a = by_seq[rec["seq_first_row"]]["t_dispatch"]
        b = by_seq[rec["seq_last_row"]]["t_dispatch"]
        assert rec["t_submit"] <= rec["t_admit"] <= a <= b <= rec["t_first_token"]
        wait, rows, tail = pieces(rec, by_seq)
        assert min(wait, rows, tail) >= 0
        assert math.isclose(wait + rows + tail, r.first_token_at - r.submitted_at, abs_tol=1e-9)


def test_the_flight_record_carries_the_three_pieces(served):
    _n, engine, reqs, recs, by_seq = served
    for r in reqs:
        phase = engine.flightrec.get(r.id)["phase_ms"]
        want = [v * 1e3 for v in pieces(recs[r.id], by_seq)]
        got = [phase["first_row_wait"], phase["prompt_rows"], phase["first_token_tail"]]
        assert got == pytest.approx(want, abs=1e-6)
        assert sum(got) == pytest.approx(phase["ttft"], abs=1e-6)


def test_a_prompt_that_waits_behind_two_others_skips_programs():
    """One row a step (the budget): two one-row prompts go first and decode
    while the third's four rows go out, one a program. The scheduler here
    waits after each step until the collector has caught up, as where the
    device runs ahead of the host: the next pass then finds the pipeline
    empty and sends decode-only chunks for the two decoding lanes before the
    third prompt's next row. Those are the skipped programs: in its stretch,
    without a row of it."""
    engine = engine_of("paged", step_token_budget=CHUNK)
    step = engine._dispatch_step

    def step_then_drain(span):
        went = step(span)
        for _ in range(1000):
            with engine._lock:
                if not engine._inflight and engine._processing is None:
                    break
            time.sleep(0.005)
        return went

    engine._dispatch_step = step_then_drain
    try:
        a, b, c = [list(range(1, 17)), list(range(2, 18)), list(range(3, 67))]
        reqs = [engine.submit(GenRequest(p, max_new_tokens=n, temperature=0.0, eos_token=-1))
                for p, n in ((a, 64), (b, 64), (c, 8))]
        for r in reqs:
            r.tokens(timeout=120)
        settle(engine)
        recs, by_seq = records(engine), steps(engine)
    finally:
        engine.close()
    first, second, third = (recs[r.id] for r in reqs)
    assert first["seq_last_row"] < second["seq_first_row"] < third["seq_first_row"]
    assert third["rows"] == third["row_steps"] == 4
    stretch = [s for s in by_seq.values() if third["seq_first_row"] <= s["seq"] <= third["seq_last_row"]]
    decode_class = [s for s in stretch if s["kind"] in ("step", "chunk", "verify")]
    skipped = [s for s in decode_class if not s["rows"]]
    assert len(decode_class) - third["row_steps"] == len(skipped) > 0
    assert all(s["kind"] == "chunk" and s["lanes"] for s in skipped)


def test_a_cached_prompt_dispatches_no_row():
    """The second time, the whole prompt is a prefix hit: no row, no seq."""
    engine = engine_of("paged", prefix_cache_mb=1)
    try:
        prompt = list(range(1, 41))
        first = serve(engine, [prompt], 4)[0]
        again = serve(engine, [prompt], 4)[0]
        recs = records(engine)
        phase = engine.flightrec.get(again.id)["phase_ms"]
    finally:
        engine.close()
    assert recs[first.id]["rows"] == 3 and recs[first.id]["cached"] == 0
    assert again.prefix_hit
    rec = recs[again.id]
    assert (rec["rows"], rec["row_steps"], rec["seq_first_row"], rec["seq_last_row"]) == (0, 0, None, None)
    assert rec["cached"] == rec["prompt"] == len(prompt)
    assert phase["ttft"] is not None and phase["first_row_wait"] is None


def test_the_prefill_span_starts_at_the_first_rows_dispatch():
    tracer = gt.new_tracer(new_mock_config({"TRACE_EXPORTER": "memory"}))
    engine = engine_of("paged", tracer=tracer, kv_label="reqtl-traced")
    try:
        (r,) = serve(engine, [list(range(1, 41))], 4)
        rec, by_seq = records(engine)[r.id], steps(engine)
        deadline = time.time() + 5
        while time.time() < deadline and not any(s.name == "llm.prefill" for s in tracer.exporter.spans):
            tracer._processor._flush()
            time.sleep(0.02)
        (span,) = [s for s in tracer.exporter.spans if s.name == "llm.prefill"]
    finally:
        engine.close()
        tracer.shutdown()
    assert (span.attributes["llm.prompt_rows"], span.attributes["llm.prompt_steps"]) == (3, 3)
    # from the first row's dispatch to the last row's fetch
    last = by_seq[rec["seq_last_row"]]
    want = last["t_fetched"] - by_seq[rec["seq_first_row"]]["t_dispatch"]
    assert (span.end_ns - span.start_ns) / 1e9 == pytest.approx(want, abs=2e-3)


def test_the_ring_is_bounded(monkeypatch):
    assert REQUEST_LOG_LEN >= 2 * 50 * 10  # two 50 s windows at ten first tokens a second
    monkeypatch.setattr(llm_mod, "REQUEST_LOG_LEN", 2)
    engine = engine_of("paged", kv_label="reqtl-ring")
    try:
        reqs = serve(engine, [list(range(1 + i, 20 + i)) for i in range(4)], 2)
        log = engine.stats()["request_log"]["records"]
    finally:
        engine.close()
    assert len(log) == 2
    ids = {r.id for r in reqs}
    kept = [rec[0] for rec in log]
    assert set(kept) <= ids
    # the newest were kept
    assert sorted(kept, key=lambda i: next(r.first_token_at for r in reqs if r.id == i)) == kept
    assert max(r.first_token_at for r in reqs) == log[-1][REQUEST_FIELDS.index("t_first_token")]


def test_a_request_requeued_before_its_first_token_starts_its_rows_again():
    r = GenRequest([1, 2, 3], max_new_tokens=2)
    r.seq_first_row, r.seq_last_row, r.rows, r.row_steps, r._first_cursor = 4, 6, 3, 3, 0
    r._requeued()
    assert (r.seq_first_row, r.seq_last_row, r.rows, r.row_steps) == (None, None, 0, 0)
    r.seq_first_row, r.seq_last_row, r.rows, r.row_steps = 7, 9, 3, 3
    r.first_token_at = 1.0
    r._requeued()  # past its first token: the admission that produced it keeps the stamps
    assert (r.seq_first_row, r.seq_last_row, r.rows, r.row_steps) == (7, 9, 3, 3)

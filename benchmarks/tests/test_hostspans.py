"""hostspans.py on a hand-built trace: one idle gap of each position, the span
each engine thread was in, the join by the trace's run ids and by order, joins
that must come out None, and the six metrics that read them."""
import importlib.util
import os
import types

import costs
import hostspans as H
import pytest
import run as R
import trace as T
import traffic

MS = 1_000_000
NS = types.SimpleNamespace


def ev(name, start_ms, end_ms, **stats):
    return NS(name=name, start_ns=int(start_ms * MS), duration_ns=int((end_ms - start_ms) * MS),
              stats=list(stats.items()))


def device_plane():
    """Five executions back to back but for 20..22; the trace begins inside the
    first and ends inside the last. Idle: 4..5 (in the first, cut: edge), 14..16
    (inside run 11), 20..22 (between), 45..47 (in the last, cut: edge)."""
    ops = [ev("%fusion.1 = f32[8] fusion(f32[8] %p)", a, b) for a, b in
           ((0, 4), (5, 10), (10, 14), (16, 20), (22, 30), (30, 40), (40, 45), (47, 50))]
    mods = [ev("jit__step(1)", 0, 10, run_id=10), ev("jit__chunk(2)", 10, 20, run_id=11),
            ev("jit__chunk(2)", 22, 30, run_id=12), ev("jit__step(1)", 30, 40, run_id=13),
            ev("jit__chunk(2)", 40, 50, run_id=14)]
    return NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops), NS(name="XLA Modules", events=mods)])


DISPATCHES = ((5, "chunk", 1, 12), (6, "step", 11, 13), (7, "chunk", 21, 14), (8, "chunk", 31, 15))


def host_plane(dispatches=DISPATCHES, enqueue=True, fetches=True):
    """The scheduler dispatches seq 5..8 (their executions: runs 12..14; seq 8's
    lies beyond the trace; runs 10 and 11 were dispatched before it)."""
    sched, runtime = [], []
    for seq, kind, t, run in dispatches:
        sched += [ev("sched.dispatch", t, t + 1, seq=seq, kind=kind, program=f"llm.{kind}"),
                  ev("dispatch.inputs", t + 0.05, t + 0.15), ev("dispatch.call", t + 0.2, t + 0.8, kind=kind),
                  ev("sched.wait", t + 1, t + 10)]
        runtime.append(ev("DoEnqueueProgram", t + 0.9, t + 0.95, run_id=run))
    sched.append(ev("sched.wait", 41, 50))
    collect = []
    for seq, t in ((3, 0), (4, 11), (5, 21), (6, 31), (7, 41)):
        collect += [ev("collect.fetch", t, t + 9.5, seq=seq, kind="chunk")] if fetches else []
        collect += [ev("collect.emit", t + 9.5, t + 10, seq=seq, kind="chunk")] if t < 41 else []
    lines = [NS(name="llm-engine-sche", events=sched), NS(name="llm-engine-coll", events=collect),
             NS(name="python3", events=[ev("sched.wait", 0, 50)])]  # another thread's events are not the engine's
    if enqueue:
        lines.append(NS(name="tfrt-non-blocking-queue/355", events=runtime))
    return NS(name="/host:CPU", lines=lines)


def reduced(data, traced_s=0.052):
    return T.reduce_planes(data, traced_s=traced_s)


def analysed(data):
    red = reduced(data)
    spans = H.host_spans(data)
    return red, spans, H.idle_table(red, spans), H.join(spans, red, H.launches(data, red, False))


def test_each_gap_has_its_position_and_its_spans():
    data = NS(planes=[host_plane(), device_plane()])
    red, spans, idle, _j = analysed(data)
    table = {k: v / MS for k, v in idle["table"].items()}
    assert table == {
        ("in_program", "sched.wait", "collect.fetch"): pytest.approx(2.0),  # 14..16
        ("between", "sched.wait", "collect.fetch"): pytest.approx(0.5),  # 20..20.5
        ("between", "sched.wait", "collect.emit"): pytest.approx(0.5),  # 20.5..21
        ("between", "sched.dispatch", "collect.fetch"): pytest.approx(1.0),  # 21..22
        ("edge", "sched.wait", "collect.fetch"): pytest.approx(3.0),  # 4..5 and 45..47: inside the two cut executions
        ("edge", H.NO_SPAN, H.NO_SPAN): pytest.approx(2.0),  # the host traced 52 ms, the ops span 50
    }
    # the three positions are the whole of the idle time
    assert sum(table.values()) == pytest.approx((red["window_s"] - red["busy_s"]) * 1e3) == pytest.approx(9.0)
    assert idle["longest"][0][:3] == (2 * MS, 14 * MS, "in_program") or idle["longest"][0][0] == 2 * MS


def test_join_by_run_id_and_by_order_agree():
    data = NS(planes=[host_plane(), device_plane()])
    _r, _s, _i, joined = analysed(data)
    want = {5: (22 * MS, 30 * MS), 6: (30 * MS, 40 * MS), 7: (40 * MS, 50 * MS)}  # seq 8 ran after the trace
    assert {seq: v[:2] for seq, v in joined.items()} == want
    assert joined[5][2:] == (int(1.2 * MS), int(1.8 * MS))  # its dispatch.call
    plain = NS(planes=[host_plane(enqueue=False), device_plane()])  # a runtime that links nothing
    _r, _s, _i, by_order = analysed(plain)
    assert by_order == joined


@pytest.mark.parametrize("why, host", [
    ("the launched program is of another kind than the dispatch",
     host_plane(((5, "step", 1, 12), (6, "step", 11, 13), (7, "chunk", 21, 14)))),
    ("two decode-class programs launched by one dispatch",
     host_plane(((5, "chunk", 1, 12), (6, "step", 11, 13), (7, "chunk", 21, 14), (8, "chunk", 21.01, 11)))),
    ("an execution that began before its dispatch.call",
     host_plane(((5, "chunk", 25, 12), (6, "step", 31, 13), (7, "chunk", 41, 14)))),
    ("a dispatch in the middle without an execution",
     host_plane(((5, "chunk", 1, 12), (6, "step", 11, 99), (7, "chunk", 21, 14)))),
    ("by order, more than one offset fits",
     host_plane(((5, "chunk", 1, 0), (6, "chunk", 2, 0), (7, "chunk", 3, 0)), enqueue=False, fetches=False)),
    ("no engine spans at all", NS(name="/host:CPU", lines=[NS(name="python3", events=[ev("x", 0, 50)])])),
])
def test_a_broken_join_is_none_never_a_guess(why, host):
    dev = device_plane()
    if why.startswith("by order"):  # every execution a chunk: nothing tells the offsets apart
        for e in dev.lines[1].events:
            e.name = "jit__chunk(2)"
    _r, spans, idle, joined = analysed(NS(planes=[host, dev]))
    assert joined is None, why
    assert sum(idle["table"].values()) == pytest.approx(9 * MS)  # positions need no join
    if why.startswith("no engine spans"):
        assert spans == {} and all(k[1:] == (H.NO_SPAN, H.NO_SPAN) for k in idle["table"])


def test_two_engines_in_one_process_give_no_names():
    host = host_plane()
    host.lines.append(NS(name="llm-engine-sche", events=[ev("sched.wait", 0, 50)]))
    assert H.host_spans(NS(planes=[host, device_plane()])) == {}


def record(seq, kind, rows=(), decode_ctx=()):
    r = dict.fromkeys(("seq", "kind", "program", "k", "depth", "lanes", "decode_ctx", "rows", "t_dispatch",
                       "t_dispatched", "t_fetch", "t_fetched", "t_emitted", "emitted"), 0)
    r.update(seq=seq, kind=kind, program="llm." + kind, rows=rows, decode_ctx=decode_ctx)
    return tuple(r.values()), tuple(r)


RECORDS = [record(3, "step", rows=((0, 64, 64),), decode_ctx=((100, 8),)),
           record(4, "chunk", decode_ctx=((108, 8), (50, 3))),
           record(5, "chunk", decode_ctx=((116, 8),)),
           record(6, "step", rows=((64, 30, 64), (0, 64, 64))),
           record(7, "chunk", decode_ctx=((124, 8),)),
           record(8, "chunk", decode_ctx=((132, 8),))]


def metric(name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(costs.HERE, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def a_run(data, monkeypatch, step_log=True):
    monkeypatch.setattr(T, "load", lambda _dir: data)
    config = traffic.load(os.path.join(costs.HERE, "configs", "mistral-7b.json"))
    model = config["model"]
    stats1 = {"step_log": {"fields": RECORDS[0][1], "records": tuple(r for r, _f in RECORDS)}} if step_log else {}
    return {"trace": {"dir": "unused", "reduced": reduced(data), "ta": 0.0, "tb": 0.052}, "stats1": stats1,
            "model": model, "family": R.load_family(config), "peaks": {"bf16_flops": 200e12, "int8_ops": 400e12},
            "config": {"engine": {"quantize": True}}, "chips": 1}


def test_the_six_metrics_read_the_trace(monkeypatch, capsys):
    run = a_run(NS(planes=[host_plane(), device_plane()]), monkeypatch)
    got = {n: metric(n).read(run) for n in ("idle_between_programs_pct", "idle_in_program_pct", "idle_edge_pct",
                                             "idle_named_pct", "dispatch_lead_ms_p50", "step_rows_mfu_pct")}
    assert got["idle_between_programs_pct"] == pytest.approx(100 * 2 / 52)
    assert got["idle_in_program_pct"] == pytest.approx(100 * 2 / 52)
    assert got["idle_edge_pct"] == pytest.approx(100 * 5 / 52)
    idle = metric("device_idle_pct").read(run)  # the accepted metric: the three sum to it
    assert got["idle_between_programs_pct"] + got["idle_in_program_pct"] + got["idle_edge_pct"] == pytest.approx(idle)
    assert got["idle_named_pct"] == pytest.approx(100.0)  # 14..16 and 20..22: both threads inside a span
    # seq 5: run 11 ended at 20, its call at 1.8; seq 6: 30 - 11.8; seq 7: 40 - 21.8
    assert got["dispatch_lead_ms_p50"] == pytest.approx(18.2)
    # every decode-class execution counts: seq 3 and 4 by their records (dispatched before the
    # trace), seq 3 and 7 (cut) in full here, since they took as long as the whole ones
    least = lambda rec: costs.least_step_seconds(  # noqa: E731
        run["model"], run["peaks"], prefill_int8=True,
        prefill_contexts=[c for s, n, _ in rec[7] for c in range(s + 1, s + n + 1)],
        decode_contexts=[c for ctx, n in rec[6] for c in range(ctx + 1, ctx + n + 1)])["seconds"]
    want = sum(least(r) for r, _f in RECORDS[:5])
    assert got["step_rows_mfu_pct"] == pytest.approx(100 * want / 0.052)
    err = capsys.readouterr().err
    assert "idle milliseconds by position and span" in err and "in_program sched.wait" in err
    assert err.count("idle by position") == 1  # parsed once per run, whatever reads it
    assert "before seq 5" in err  # the gap 20..22 lies before seq 5's execution


def test_a_cut_execution_counts_by_its_share(monkeypatch):
    dev = device_plane()
    dev.lines[1].events[0] = ev("jit__step(1)", 5, 10, run_id=10)  # the trace began 5 ms into it
    dev.lines[0].events[:2] = [ev("%fusion.1 = f32[8] fusion(f32[8] %p)", 5, 10)]
    run = a_run(NS(planes=[host_plane(), dev]), monkeypatch)
    runs = H.with_leading(H.analyse(run)["join"], run["trace"]["reduced"], H.records_by_seq(run))
    assert runs[3] == (5 * MS, 10 * MS, True) and runs[4][2] is False and runs[7][2] is True
    full = a_run(NS(planes=[host_plane(), device_plane()]), monkeypatch)
    part, whole = metric("step_rows_mfu_pct").read(run), metric("step_rows_mfu_pct").read(full)
    assert part < whole * 0.052 / 0.047 * 0.999  # seq 3 counts half: 5 of the 10 ms a whole step takes


def test_a_program_without_spans_or_records_leaves_the_metrics_out(monkeypatch):
    """The parent of the PR that brought the spans: positions read, nothing raises."""
    bare = NS(name="/host:CPU", lines=[NS(name="python3", events=[ev("x", 0, 50)])])
    run = a_run(NS(planes=[bare, device_plane()]), monkeypatch, step_log=False)
    assert metric("idle_edge_pct").read(run) == pytest.approx(100 * 5 / 52)
    for name in ("idle_named_pct", "dispatch_lead_ms_p50", "step_rows_mfu_pct"):
        assert metric(name).read(run) is None
    with_spans = a_run(NS(planes=[host_plane(), device_plane()]), monkeypatch, step_log=False)
    assert metric("step_rows_mfu_pct").read(with_spans) is None  # spans but no records
    assert metric("idle_between_programs_pct").read({"trace": None}) is None  # an untraced run


def test_records_that_contradict_the_trace_give_none(monkeypatch):
    run = a_run(NS(planes=[host_plane(), device_plane()]), monkeypatch)
    fields = RECORDS[0][1]
    wrong = list(run["stats1"]["step_log"]["records"])
    wrong[1] = record(4, "step")[0]  # the execution before seq 5 is a jit__chunk
    run["stats1"] = {"step_log": {"fields": fields, "records": tuple(wrong)}}
    assert metric("step_rows_mfu_pct").read(run) is None

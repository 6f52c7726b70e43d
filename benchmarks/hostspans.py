"""The engine's host spans beside the device's lines: where idle time lies.

The program writes spans into the profiler's trace (`gofr_tpu.profiling.engine_span`):
on the thread `llm-engine-sched` one of `sched.housekeep | sched.admit | sched.plan |
sched.dispatch | sched.wait` at every moment, on `llm-engine-collect` one of
`collect.wait | collect.fetch | collect.emit`; `sched.dispatch` and the collector's
two carry the `seq` of the program they handle, which is also the key of the engine's
step records (`stats()["step_log"]`). `analyse(run)` reads the trace under
`run["trace"]["dir"]` once per run and gives

- every device idle gap of `run["trace"]["reduced"]` a position: `edge` (before the
  first or after the last whole program execution of the traced stretch, and what the
  host's stretch holds beyond the ops' own span), `in_program` (inside an execution
  on the "XLA Modules" line), `between` (the rest), and the span each engine thread
  was in meanwhile;
- the join of `sched.dispatch` spans to device executions, checked, or None.

A program without spans (an older one) gives positions but no names and no join: the
readers that need those return None and their metrics are left out. The table of idle
time by position and span goes to standard error.
"""

from __future__ import annotations

import bisect
import sys

import trace as T

SCHED, COLLECT = "llm-engine-sched", "llm-engine-collect"  # the OS keeps 15 bytes of each
TOP = ("sched.housekeep", "sched.admit", "sched.plan", "sched.dispatch", "sched.wait",
       "collect.wait", "collect.fetch", "collect.emit")
INNER = ("dispatch.inputs", "dispatch.call")
POSITIONS = ("between", "in_program", "edge")
# the decode-class programs by the record's `kind`: the jitted functions are `_step`,
# `_chunk` (`_chunk_op` on the ring) and `_verify*`
MODULE_OF_KIND = {"step": "jit__step", "chunk": "jit__chunk", "verify": "jit__verify"}
PIPELINE = 8  # more programs than any `lookahead` keeps in flight
NO_SPAN = "(no span)"


def say(msg: str) -> None:
    print("[hostspans] " + msg, file=sys.stderr, flush=True)


class Span:
    __slots__ = ("name", "start", "end", "stats")

    def __init__(self, name, start, end, stats):
        self.name, self.start, self.end, self.stats = name, start, end, stats


def thread_line(line_name: str, thread: str) -> bool:
    """The profiler names a host line after the OS thread, which keeps the
    first 15 bytes of the Python name."""
    return len(line_name) >= 15 and thread.startswith(line_name.split("/")[0])


def host_spans(data) -> dict:
    """{thread: [Span, ...] sorted by start} of the engine's own spans on the
    two engine threads; a thread's line missing or doubled (two engines in one
    process) gives an empty dict: no names, no join."""
    wanted = set(TOP) | set(INNER)
    out: dict[str, list] = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            thread = next((t for t in (SCHED, COLLECT) if thread_line(line.name, t)), None)
            if thread is None:
                continue
            spans = [Span(ev.name, int(ev.start_ns), int(ev.start_ns) + int(ev.duration_ns), dict(ev.stats))
                     for ev in line.events if ev.name in wanted]
            if not spans:
                continue
            if thread in out:
                return {}
            out[thread] = sorted(spans, key=lambda s: s.start)
    return out if len(out) == 2 else {}


def top_level(spans: list) -> tuple[list, list]:
    """(starts, spans) of a thread's top-level spans, for bisecting."""
    tops = [s for s in spans if s.name in TOP]
    return [s.start for s in tops], tops


def span_at(index: tuple[list, list], t: int) -> Span | None:
    starts, tops = index
    i = bisect.bisect_right(starts, t) - 1
    return tops[i] if i >= 0 and tops[i].end > t else None


def cuts(index: tuple[list, list], a: int, b: int) -> list:
    """The boundaries of a thread's top-level spans strictly inside (a, b)."""
    starts, tops = index
    out = []
    i = max(0, bisect.bisect_right(starts, a) - 1)
    while i < len(tops) and tops[i].start < b:
        out += [t for t in (tops[i].start, tops[i].end) if a < t < b]
        i += 1
    return out


def executions(dev: dict) -> list:
    """Every program execution on a device's "XLA Modules" line as
    (start, end, base name), by start. One that lies inside the one before it is
    dropped: the CPU stand-in's host call event comes twice, nested."""
    out: list = []
    for run in sorted((s, s + d, name) for name, runs in dev["modules"].items() for s, d in runs):
        if not out or run[0] >= out[-1][1]:
            out.append(run)
    return out


def is_cut(run: tuple, dev: dict) -> bool:
    """The trace began or ended inside this execution: its event starts with the
    device's first recorded operation or ends with its last (within a microsecond)."""
    return run[0] <= dev["start_ns"] + 1000 or run[1] >= dev["start_ns"] + dev["span_ns"] - 1000


def positions_of(gap: tuple[int, int], runs: list, run_starts: list, first: int, last: int) -> list:
    """One idle gap cut at the executions' edges: [(start, end, position)]. Before
    `first` (the first whole execution's start) and after `last` (the last whole
    one's end) is `edge`; inside an execution `in_program`; the rest `between`."""
    g0, g1 = gap[0], gap[0] + gap[1]
    if first >= last:
        return [(g0, g1, "edge")]
    out = []
    if g0 < first:
        out.append((g0, min(g1, first), "edge"))
    if g1 > last:
        out.append((max(g0, last), g1, "edge"))
    a, b = max(g0, first), min(g1, last)
    if a >= b:
        return out
    # executions on one device do not overlap: walk those that touch [a, b)
    i = max(0, bisect.bisect_right(run_starts, a) - 1)
    t = a
    while t < b and i < len(runs):
        s, e, _n = runs[i]
        if e <= t:
            i += 1
            continue
        if s > t:
            out.append((t, min(s, b), "between"))
            t = min(s, b)
            continue
        out.append((t, min(e, b), "in_program"))
        t = min(e, b)
        i += 1
    if t < b:
        out.append((t, b, "between"))
    return out


def idle_table(red: dict, spans: dict) -> dict:
    """Idle nanoseconds by (position, sched span, collect span), mean over the
    devices, and each device's longest pieces. The traced window beyond the ops'
    own span (`window_s` less `span_s`) is `edge` with no span: the trace does not
    say where on its clock the host's stretch began."""
    sched = top_level(spans.get(SCHED, []))
    collect = top_level(spans.get(COLLECT, []))
    named = bool(spans)
    table: dict[tuple, float] = {}
    longest: list = []
    n = len(red["devices"])
    for dev in red["devices"]:
        runs = executions(dev)
        run_starts = [r[0] for r in runs]
        whole = [r for r in runs if not is_cut(r, dev)]
        first = min((r[0] for r in whole), default=0)
        last = max((r[1] for r in whole), default=0)
        for gap in dev["gaps"]:
            for a, b, pos in positions_of(gap, runs, run_starts, first, last):
                ts = sorted({a, b, *cuts(sched, a, b), *cuts(collect, a, b)}) if named else [a, b]
                for t0, t1 in zip(ts, ts[1:]):
                    mid = (t0 + t1) // 2
                    s, c = span_at(sched, mid), span_at(collect, mid)
                    key = (pos, s.name if s else NO_SPAN, c.name if c else NO_SPAN)
                    table[key] = table.get(key, 0.0) + (t1 - t0) / n
                longest.append((b - a, a, pos, dev["plane"]))
        outside = max(0.0, red["window_s"] * 1e9 - dev["span_ns"])
        if outside:
            key = ("edge", NO_SPAN, NO_SPAN)
            table[key] = table.get(key, 0.0) + outside / n
    longest.sort(reverse=True)
    return {"table": table, "longest": longest[:10]}


def launches(data, red: dict, stand_in: bool) -> list:
    """[(host time, (start, end, name) of the execution it launched)] by time: the
    trace's own link between a host call and a device execution. On the TPU the
    runtime's `DoEnqueueProgram` host events and the "XLA Modules" events carry
    the same `run_id`. On the CPU stand-in an "execution" is the host's own call
    event, so it is its own launch."""
    runs = executions(red["devices"][0])
    if stand_in:
        return [(r[0], r) for r in runs]
    run_of = {}
    for plane in data.planes:
        if T.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == T.MODULES_LINE:
                    for ev in line.events:
                        rid = dict(ev.stats).get("run_id")
                        if rid is not None:
                            run_of[rid] = int(ev.start_ns)
    by_start = {r[0]: r for r in runs}
    out = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == "DoEnqueueProgram":
                        r = by_start.get(run_of.get(dict(ev.stats).get("run_id")))
                        if r is not None:
                            out.append((int(ev.start_ns), r))
    return sorted(out)


def join(spans: dict, red: dict, launched: list) -> dict | None:
    """{seq: (start, end, call start, call end)}: the execution on the device of
    each `sched.dispatch` span of the trace, or None where the two sides cannot be
    matched for certain. A dispatch's execution is the one decode-class program
    launched between the start of its `dispatch.call` and the start of the
    scheduler's next call (`launched`: the trace's run ids). Where the trace
    links nothing, the order within the programs' names decides: one device runs
    the engine's programs as dispatched, so the i-th decode-class execution after
    an offset is the i-th dispatch; the offset is the only one at which every
    pair agrees in name, every execution starts after its call began and ends
    before its `collect.fetch` returned. Checked either way: dispatches without
    an execution only at the end of the trace, executions without a dispatch only
    at its start, a pipeline's depth of either at most, and no execution before
    its call."""
    if not spans or len(red["devices"]) != 1:
        return None
    names = tuple(MODULE_OF_KIND.values())
    calls = [s for s in spans[SCHED] if s.name == "dispatch.call"]
    call_starts = [s.start for s in calls]
    host = []  # (seq, module name, call start, call end, next call's start)
    for d in spans[SCHED]:
        if d.name != "sched.dispatch" or "seq" not in d.stats or str(d.stats.get("kind")) not in MODULE_OF_KIND:
            continue
        i = bisect.bisect_left(call_starts, d.start)
        if i >= len(calls) or calls[i].end > d.end:
            return None  # a dispatch that carries a seq made a call
        nxt = calls[i + 1].start if i + 1 < len(calls) else float("inf")
        host.append((int(d.stats["seq"]), MODULE_OF_KIND[str(d.stats["kind"])], calls[i].start, calls[i].end, nxt))
    runs = [r for r in executions(red["devices"][0]) if r[2].startswith(names)]
    if not host or not runs:
        return None
    pairs = []
    if launched:
        times = [t for t, _r in launched]
        for h in host:
            mine = [r for _t, r in launched[bisect.bisect_left(times, h[2]):bisect.bisect_left(times, h[4])]
                    if r[2].startswith(names)]
            if len(mine) > 1 or (mine and not mine[0][2].startswith(h[1])):
                return None
            pairs.append((h, mine[0] if mine else None))
    else:
        fetch_end = {int(s.stats["seq"]): s.end for s in spans[COLLECT]
                     if s.name == "collect.fetch" and "seq" in s.stats}
        fits = []
        for off in range(-PIPELINE, PIPELINE + 1):  # runs[i + off] is host[i]
            cand = [(h, runs[i + off] if 0 <= i + off < len(runs) else None) for i, h in enumerate(host)]
            if any(r for _h, r in cand) and all(
                    r is None or (r[2].startswith(h[1]) and r[0] >= h[2] and r[1] <= fetch_end.get(h[0], r[1]))
                    for h, r in cand):
                fits.append(cand)
        if len(fits) != 1:
            return None
        pairs = fits[0]
    got = [r is not None for _h, r in pairs]
    n = sum(got)
    joined = {h[0]: (r[0], r[1], h[2], h[3]) for h, r in pairs if r is not None}
    first = min((v[0] for v in joined.values()), default=None)
    if (n == 0 or got != [True] * n + [False] * (len(got) - n) or len(got) - n > PIPELINE
            or any(v[0] < v[2] for v in joined.values())
            or sum(r[0] < first for r in runs) > PIPELINE
            or len(runs) - sum(r[0] < first for r in runs) != n):
        return None
    return joined


def with_leading(joined: dict, red: dict, records: dict) -> dict | None:
    """{seq: (start, end, cut)} for EVERY decode-class execution of the trace. The
    ones at its start were dispatched before it began and have no span; the
    device runs programs as dispatched, so the execution before seq s is the
    nearest earlier seq whose record is of a decode-class kind, and its module's
    name has to agree. `cut`: the trace began or ended inside the execution."""
    dev = red["devices"][0]
    runs = [r for r in executions(dev) if r[2].startswith(tuple(MODULE_OF_KIND.values()))]
    out = {seq: v[:2] for seq, v in joined.items()}
    first = min(v[0] for v in out.values())
    seq = min(out)
    for r in reversed([r for r in runs if r[0] < first]):
        seq -= 1
        while seq in records and records[seq]["kind"] not in MODULE_OF_KIND:
            seq -= 1  # an admission wave's programs are no decode-class executions
        if seq not in records or not r[2].startswith(MODULE_OF_KIND[records[seq]["kind"]]):
            return None
        out[seq] = (r[0], r[1])
    return {seq: (s, e, is_cut((s, e), dev)) for seq, (s, e) in out.items()}


def analyse(run: dict) -> dict | None:
    """Once per run: the idle table and the join; None without a trace."""
    tr = run.get("trace")
    if not tr:
        return None
    if "hostspans" in tr:
        return tr["hostspans"]
    tr["hostspans"] = None
    try:
        data = T.load(tr["dir"])
    except (FileNotFoundError, OSError) as e:
        say(f"no trace to read: {e}")
        return None
    red = tr["reduced"]
    stand_in = not any(T.DEVICE_PLANE.match(p.name) for p in data.planes)
    spans = host_spans(data)
    idle = idle_table(red, spans)
    joined = join(spans, red, launches(data, red, stand_in)) if spans else None
    window_ns = red["window_s"] * 1e9
    by_pos = {p: sum(v for k, v in idle["table"].items() if k[0] == p) for p in POSITIONS}
    out = {"spans": spans, "idle": idle, "join": joined, "window_ns": window_ns, "by_position": by_pos}
    tr["hostspans"] = out
    report(out)
    return out


def report(out: dict) -> None:
    w = out["window_ns"]
    say(f"idle by position, of a traced window of {w / 1e9:.3f} s: " + ", ".join(
        f"{p} {out['by_position'][p] / 1e9:.4f} s ({100 * out['by_position'][p] / w:.3f}%)" for p in POSITIONS))
    if not out["spans"]:
        say("the trace holds no engine spans on the two engine threads: no names, no join")
    say("idle milliseconds by position and span (sched thread | collect thread), rows over 1% of the idle time:")
    total = sum(out["idle"]["table"].values())
    for (pos, s, c), ns in sorted(out["idle"]["table"].items(), key=lambda kv: -kv[1]):
        if ns >= total / 100:
            say(f"  {ns / 1e6:10.3f} ms {100 * ns / w:8.4f}%  {pos:10s} {s:16s} | {c}")
    joined = out["join"]
    by_start = sorted((v[0], seq) for seq, v in joined.items()) if joined else []
    starts = [s for s, _seq in by_start]
    for length, start, pos, plane in out["idle"]["longest"][:5]:
        i = bisect.bisect_left(starts, start)
        nxt = f"before seq {by_start[i][1]}" if i < len(by_start) else "before no joined program"
        say(f"  gap {length / 1e6:9.3f} ms {pos:10s} {nxt} ({plane})")
    say(f"join: {len(joined)} sched.dispatch spans matched to executions" if joined
        else "join: none (no spans, or the two sides could not be matched for certain)")


def idle_pct(run: dict, position: str) -> float | None:
    out = analyse(run)
    return None if out is None else 100.0 * out["by_position"][position] / out["window_ns"]


def records_by_seq(run: dict) -> dict | None:
    """The engine's step records as {seq: {field: value}}, from the `stats()` taken
    when the window closed; None where the program keeps none."""
    log = (run.get("stats1") or {}).get("step_log")
    if not log:
        return None
    fields = log["fields"]
    return {rec[fields.index("seq")]: dict(zip(fields, rec)) for rec in log["records"]}

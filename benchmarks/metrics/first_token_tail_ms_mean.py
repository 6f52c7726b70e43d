"""The dispatch of the program whose row finished the request's prompt -> its
first token handed over by the collector, mean over the requests whose first
token fell in the window: the pipeline's tail. The join is
`first_row_wait_ms_mean`'s (`joined`, called as a module).

Where the trace's `sched.dispatch` spans join its executions
(`hostspans.join`), standard error also gets the tail in three parts, over the
requests whose last row's program the trace holds: dispatch -> that program's
start on the device (the pipeline's depth), its execution, and its end -> the
first token (fetch and emit). The trace's clock is put on the step records'
by the median of (end of `dispatch.call` - the record's `t_dispatched`) over
the joined programs."""
import importlib.util
import os
import statistics

import hostspans

_spec = importlib.util.spec_from_file_location(
    "request_timeline_join", os.path.join(os.path.dirname(__file__), "first_row_wait_ms_mean.py"))
_timeline = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_timeline)

META = {"name": "first_token_tail_ms_mean", "unit": "ms", "better": "lower", "source": "program_span",
        "layer": "Scheduler (dispatch/collect pipeline)", "moves": "ttft_ms_mean",
        "workloads": ["mistral-7b.long-closed", "qwen2-7b.reason-closed", "glm-4.7-flash.think-closed",
                      "k-exaone-236b-a23b.mixed-closed", "olmo-hybrid-7b.chat-closed"]}


def tail_split(got: list, join: dict, steps: dict) -> tuple | None:
    """(requests, mean ms of dispatch -> execution start, of the execution, of
    its end -> the first token) over the requests whose last row's program is
    in `join` ({seq: (start, end, call start, call end)}, trace nanoseconds);
    None where none is."""
    offsets = [v[3] / 1e9 - steps[seq]["t_dispatched"] for seq, v in join.items() if seq in steps]
    parts = []
    if offsets:
        off = statistics.median(offsets)
        for r, _a, b, _n in got:
            v = join.get(b["seq"])
            if v is not None:
                start, end = v[0] / 1e9 - off, v[1] / 1e9 - off
                parts.append((start - b["t_dispatch"], end - start, r["t_first_token"] - end))
    if not parts:
        return None
    return (len(parts), *(1e3 * sum(p[i] for p in parts) / len(parts) for i in range(3)))


def read(run):
    got = _timeline.joined(run)
    if got is None:
        return None
    out = hostspans.analyse(run)
    if out is not None and out["join"]:
        split = tail_split(got, out["join"], hostspans.records_by_seq(run))
        if split is None:
            _timeline.say("tail split: the trace holds no request's last-row program")
        else:
            n, depth, execution, fetch = split
            _timeline.say(f"tail split over {n} requests whose last row's program the trace holds: "
                          f"dispatch -> execution start {depth:.3f} ms (the pipeline's depth), "
                          f"execution {execution:.3f} ms, end -> first token {fetch:.3f} ms (fetch and emit)")
    return 1e3 * sum(r["t_first_token"] - b["t_dispatch"] for r, _a, b, _n in got) / len(got)

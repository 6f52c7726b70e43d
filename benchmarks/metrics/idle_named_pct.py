"""Of the idle time between and inside program executions, the share during
which BOTH engine threads were inside one of their named spans: how much of the
idle time the step timeline explains. 100 where there is no such idle time."""
import hostspans

META = {"name": "idle_named_pct", "unit": "%", "better": "higher", "source": "program_span",
        "layer": "Scheduler (dispatch/collect pipeline)", "moves": "tokens_per_s",
        "workloads": ["qwen2-7b.reason-closed", "mistral-7b.long-closed"]}


def read(run):
    out = hostspans.analyse(run)
    if out is None or not out["spans"]:
        return None
    inner = {k: v for k, v in out["idle"]["table"].items() if k[0] != "edge"}
    total = sum(inner.values())
    named = sum(v for k, v in inner.items() if hostspans.NO_SPAN not in k)
    return 100.0 * named / total if total else 100.0

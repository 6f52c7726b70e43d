"""How far ahead of the device the scheduler runs: per joined execution, the end
of the previous execution on the device minus the end of this one's
`dispatch.call` on the host, median. Positive: the program was queued before the
device needed it; negative: the device waited for the host."""
import bisect

import hostspans
import stats as S

META = {"name": "dispatch_lead_ms_p50", "unit": "ms", "better": "higher", "source": "program_span",
        "layer": "Scheduler (dispatch/collect pipeline)", "moves": "tokens_per_s",
        "workloads": ["qwen2-7b.reason-closed", "mistral-7b.long-closed"]}


def read(run):
    out = hostspans.analyse(run)
    if out is None or not out["join"]:
        return None
    ends = sorted(e for _s, e, _n in hostspans.executions(run["trace"]["reduced"]["devices"][0]))
    leads = []
    for start, _end, _call_start, call_end in out["join"].values():
        i = bisect.bisect_right(ends, start)  # the execution that ended last before this one began
        if i:
            leads.append((ends[i - 1] - call_end) / 1e6)
    return S.percentile(leads, 50) if leads else None

"""Submit -> admission by the scheduler, per request, median over the
requests whose first token fell in the window."""
import stats as S

META = {"name": "queue_wait_ms_p50", "unit": "ms", "better": "lower", "source": "program_span",
        "layer": "Scheduler (LLMEngine._schedule_loop, admission)", "moves": "ttft_ms_mean",
        "workloads": ["qwen2-7b.reason-closed", "mistral-7b.long-closed"]}


def read(run):
    waits = [(r.admitted_at - r.sent) * 1e3 for r in run["records"]
             if r.admitted_at is not None and r.stamps
             and S.in_window(r.stamps[0], run["t0"], run["t1"])]
    return S.percentile(waits, 50) if waits else None

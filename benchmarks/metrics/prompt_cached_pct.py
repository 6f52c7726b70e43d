"""Of the prompt tokens of the requests whose first token fell in the window,
the share served from the prefix cache (the radix tree over the paged pool):
the `cached` over the `prompt` of the engine's request records
(`stats()["request_log"]`, taken when the window closed). A program that
keeps no request records reads nothing."""
import stats as S

META = {"name": "prompt_cached_pct", "unit": "%", "better": "higher", "source": "program_counter",
        "layer": "KV manager (kvcache/paged.py)", "moves": "ttft_ms_mean",
        "workloads": ["deepseek-v3.2.docs-closed"]}


def read(run):
    rlog = (run.get("stats1") or {}).get("request_log")
    if not rlog:
        return None
    reqs = [dict(zip(rlog["fields"], rec)) for rec in rlog["records"]]
    reqs = [r for r in reqs if S.in_window(r["t_first_token"], run["t0"], run["t1"])]
    prompt = sum(r["prompt"] for r in reqs)
    if not prompt:
        return None
    return 100.0 * sum(r["cached"] for r in reqs) / prompt

"""Paged pool: blocks in use over blocks in the pool, sampled once a second
in the window, mean."""

META = {"name": "kv_blocks_used_pct", "unit": "%", "better": "higher", "source": "program_counter",
        "layer": "KV manager (kvcache/paged.py)", "moves": "tokens_per_s",
        "workloads": ["qwen2-7b.reason-closed"]}


def read(run):
    xs = [s["blocks_in_use"] / s["pool_blocks"] for s in run["kv_samples"]
          if s.get("layout") == "paged" and s.get("pool_blocks")]
    return 100.0 * sum(xs) / len(xs) if xs else None

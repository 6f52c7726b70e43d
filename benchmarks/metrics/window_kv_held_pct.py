"""Mixed stack, the window layers' pool: blocks they hold over the blocks the
same slots would hold had none been given back behind the window
(`stats()["kvcache"]["kinds"]["window"]`: `blocks_in_use` /
`blocks_unreclaimed`), sampled once a second in the window as
`kv_blocks_used_pct` is, mean. Well under 100 where contexts pass the window;
100 would mean a window layer keeps its whole context. An engine whose cache
reports no kinds (one pool for all layers) reads nothing."""

META = {"name": "window_kv_held_pct", "unit": "%", "better": "lower", "source": "program_counter",
        "layer": "KV manager (kvcache/paged.py)", "moves": "tokens_per_s",
        "workloads": ["k-exaone-236b-a23b.mixed-closed"]}


def read(run):
    xs = []
    for s in run["kv_samples"]:
        w = (s.get("kinds") or {}).get("window")
        if w and w.get("blocks_unreclaimed"):
            xs.append(w["blocks_in_use"] / w["blocks_unreclaimed"])
    return 100.0 * sum(xs) / len(xs) if xs else None

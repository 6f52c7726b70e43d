"""Of the rows in the decode tokens' contexts, the share their attention read:
rows attended over rows in context, summed over the window's decode tokens'
layers, from the engine's `stats()["attention"]["sparse"]` at the window's two
ends. ~15% at 13k-token contexts and a top-2,048; 100% says the selection
never engaged. An engine that reports no sparse counts reads nothing."""

META = {"name": "dsa_rows_read_pct", "unit": "%", "better": "lower", "source": "program_counter",
        "layer": "Sparse attention (ops/attention.py dsa_paged_chunk_decode_attention)", "moves": "tpot_ms_mean",
        "workloads": ["deepseek-v3.2.docs-closed"]}


def _sparse(stats):
    return ((stats or {}).get("attention") or {}).get("sparse")


def read(run):
    a, b = _sparse(run["stats0"]), _sparse(run["stats1"])
    if not a or not b:
        return None
    context = b["rows_in_context"] - a["rows_in_context"]
    if context <= 0:
        return None
    return 100.0 * (b["rows_selected"] - a["rows_selected"]) / context

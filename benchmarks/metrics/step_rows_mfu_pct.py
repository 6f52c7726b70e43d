"""step_mfu_pct with its numerator READ, not counted: the same formula and
denominator (the family's `least_step_seconds` over the traced device window and the
chips), the prompt and decode tokens taken from the `rows` and `decode_ctx` of
the engine's step records (`stats()["step_log"]`) of the programs whose
executions the trace holds (joined by hostspans.py). A row (start, n, shape) is
n prompt tokens at contexts start+1..start+n; a decoding lane (context, n) emitted
n tokens at contexts context+1..context+n. The executions that the trace cuts at
its two ends count by the share of them it holds: their recorded time over the
mean time of the whole executions of the same program."""
import hostspans

META = {"name": "step_rows_mfu_pct", "unit": "%", "better": "higher", "source": "device_trace",
        "layer": "Device, whole step", "moves": "tokens_per_s",
        "workloads": ["qwen2-7b.reason-closed", "mistral-7b.long-closed"]}


def read(run):
    out = hostspans.analyse(run)
    records = hostspans.records_by_seq(run)
    if out is None or not out["join"] or not records:
        return None
    runs = hostspans.with_leading(out["join"], run["trace"]["reduced"], records)
    if runs is None or any(seq not in records for seq in runs):
        return None  # a program's fetch failed, or the ring is shorter than the run
    whole: dict = {}  # program -> device times of its whole executions
    for seq, (start, end, cut) in runs.items():
        if not cut:
            whole.setdefault(records[seq]["program"], []).append(end - start)
    seconds = 0.0
    for seq, (start, end, cut) in runs.items():
        rec = records[seq]
        times = whole.get(rec["program"])
        if cut and not times:
            continue  # nothing to measure its share by
        least = run["family"].least_step_seconds(
            run["model"], run["peaks"],
            prefill_contexts=[c for start_, n, _shape in rec["rows"] for c in range(start_ + 1, start_ + n + 1)],
            decode_contexts=[c for ctx, n in rec["decode_ctx"] for c in range(ctx + 1, ctx + n + 1)],
            prefill_int8=bool(run["config"]["engine"].get("quantize")))
        share = min(1.0, (end - start) / (sum(times) / len(times))) if cut else 1.0
        seconds += share * least["seconds"]
    return 100.0 * seconds / (run["trace"]["reduced"]["window_s"] * run["chips"])

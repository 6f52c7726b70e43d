"""The routed experts' matmuls' share of their roofline: the family's
`moe_least_seconds` (the larger of streaming each touched expert's int8 weights
once at the peak bandwidth and of the routed pairs' FLOPs at the bf16 peak) of
the step records' `moe_pairs` / `moe_touched`, over the programs whose
executions the trace holds (joined by hostspans.py; one that the trace cuts
counts by the share of it the trace holds), divided by the summed device time
of the grouped-matmul kernel. A program that records no pairs (an older one,
or a dense model) reads nothing."""
import hostspans
import trace as T

KERNEL = r"moe_grouped_matmul"  # the pallas_call's name (ops/grouped.py)

META = {"name": "moe_ffn_roofline_pct", "unit": "%", "better": "higher", "source": "device_trace",
        "layer": "Kernels (ops/grouped.py _gmm_kernel)", "moves": "tokens_per_s",
        "workloads": ["glm-4.7-flash.think-closed"]}


def read(run):
    least = getattr(run["family"], "moe_least_seconds", None)
    kernel_s, count = T.op_seconds(run["trace"]["reduced"], KERNEL)
    out = hostspans.analyse(run)
    records = hostspans.records_by_seq(run)
    if least is None or not count or kernel_s <= 0 or out is None or not out["join"] or not records:
        return None
    runs = hostspans.with_leading(out["join"], run["trace"]["reduced"], records)
    if runs is None or any(seq not in records or "moe_pairs" not in records[seq] for seq in runs):
        return None
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
        share = min(1.0, (end - start) / (sum(times) / len(times))) if cut else 1.0
        seconds += share * least(run["model"], run["peaks"], pairs=rec["moe_pairs"], touched=rec["moe_touched"])
    return 100.0 * seconds / kernel_s if seconds > 0 else None

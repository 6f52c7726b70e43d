"""The paged-decode kernel's share of its roofline, bound by HBM bandwidth:
the KV bytes the sub-window's decode tokens had to read (each token its whole
context, from the benchmark's own records, whatever implements the read) over
the peak bandwidth, divided by the kernel's summed device time."""
import stats as S
import trace as T

# no name yet: the Mosaic call whose first operand is the 2-D s32 block table
KERNEL = r'custom-call\(s32\[\d+,\d+\].*custom_call_target="tpu_custom_call"'

META = {"name": "paged_decode_roofline_pct", "unit": "%", "better": "higher", "source": "device_trace",
        "layer": "Kernels (ops/attention.py _paged_decode_kernel)", "moves": "tokens_per_s",
        "workloads": ["qwen2-7b.reason-closed"]}


def read(run):
    tr = run["trace"]
    kernel_s, count = T.op_seconds(tr["reduced"], KERNEL)
    if not count or kernel_s <= 0:
        return None
    contexts = S.decode_contexts(run["records"], tr["ta"], tr["tb"])
    bytes_per_s = run["family"].decode_kv_read_bytes(run["model"], contexts) / (tr["tb"] - tr["ta"])
    least_share = bytes_per_s / run["peaks"]["hbm_bytes_per_s"]  # of each second
    return 100.0 * least_share / (kernel_s / tr["reduced"]["span_s"])  # of each second the ops span

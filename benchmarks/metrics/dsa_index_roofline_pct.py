"""The lightning indexer's decode scoring kernel's share of its roofline, bound
by HBM bandwidth: the index keys the sub-window's decode tokens had to read
(every row of each token's context in every layer, 256 B a row for
DeepSeek-V3.2, the family's `index_read_bytes`, from the benchmark's own
records) over the peak bandwidth, divided by the kernel's summed device time.
The kernel copies whole pages of the rope array, whose rows hold the rope key
beside the index key, so it reads honestly below half of its roofline. A
program without the kernel reads nothing."""
import stats as S
import trace as T

KERNEL = r"dsa_index_decode"  # the pallas_call's name (ops/attention.py)

META = {"name": "dsa_index_roofline_pct", "unit": "%", "better": "higher", "source": "device_trace",
        "layer": "Kernels (ops/attention.py _dsa_index_kernel)", "moves": "tpot_ms_mean",
        "workloads": ["deepseek-v3.2.docs-closed"]}


def read(run):
    tr = run["trace"]
    need = getattr(run["family"], "index_read_bytes", None)
    kernel_s, count = T.op_seconds(tr["reduced"], KERNEL)
    if need is None or not count or kernel_s <= 0:
        return None
    contexts = S.decode_contexts(run["records"], tr["ta"], tr["tb"])
    bytes_per_s = need(run["model"], contexts) / (tr["tb"] - tr["ta"])
    least_share = bytes_per_s / run["peaks"]["hbm_bytes_per_s"]  # of each second
    return 100.0 * least_share / (kernel_s / tr["reduced"]["span_s"])  # of each second the ops span

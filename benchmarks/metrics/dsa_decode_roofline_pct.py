"""The chosen rows' latent attention kernel's share of its roofline, bound by
HBM bandwidth: the rows the sub-window's decode tokens attended (min(2,048,
context) a layer, 1,280 B a row for DeepSeek-V3.2: latent and rope key, the
family's `sparse_read_bytes`, from the benchmark's own records) over the peak
bandwidth, divided by the kernel's summed device time. A program without the
kernel reads nothing."""
import stats as S
import trace as T

KERNEL = r"mla_sparse_decode"  # the pallas_call's name (ops/attention.py)

META = {"name": "dsa_decode_roofline_pct", "unit": "%", "better": "higher", "source": "device_trace",
        "layer": "Kernels (ops/attention.py _mla_sparse_kernel)", "moves": "tpot_ms_mean",
        "workloads": ["deepseek-v3.2.docs-closed"]}


def read(run):
    tr = run["trace"]
    need = getattr(run["family"], "sparse_read_bytes", None)
    kernel_s, count = T.op_seconds(tr["reduced"], KERNEL)
    if need is None or not count or kernel_s <= 0:
        return None
    contexts = S.decode_contexts(run["records"], tr["ta"], tr["tb"])
    bytes_per_s = need(run["model"], contexts) / (tr["tb"] - tr["ta"])
    least_share = bytes_per_s / run["peaks"]["hbm_bytes_per_s"]  # of each second
    return 100.0 * least_share / (kernel_s / tr["reduced"]["span_s"])  # of each second the ops span

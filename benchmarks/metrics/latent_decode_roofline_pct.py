"""The latent paged-decode kernel's share of its roofline, bound by HBM
bandwidth: the cache bytes the sub-window's decode tokens had to read (each
token its whole context at the family's bytes a row, 1,152 B a layer for
GLM-4.7-Flash whatever the layout pads, from the benchmark's own records) over
the peak bandwidth, divided by the kernel's summed device time."""
import stats as S
import trace as T

KERNEL = r"mla_paged_decode"  # the pallas_call's name (ops/attention.py)

META = {"name": "latent_decode_roofline_pct", "unit": "%", "better": "higher", "source": "device_trace",
        "layer": "Kernels (ops/attention.py _mla_paged_decode_kernel)", "moves": "tokens_per_s",
        "workloads": ["glm-4.7-flash.think-closed"]}


def read(run):
    tr = run["trace"]
    kernel_s, count = T.op_seconds(tr["reduced"], KERNEL)
    if not count or kernel_s <= 0:
        return None
    contexts = S.decode_contexts(run["records"], tr["ta"], tr["tb"])
    bytes_per_s = run["family"].decode_kv_read_bytes(run["model"], contexts) / (tr["tb"] - tr["ta"])
    least_share = bytes_per_s / run["peaks"]["hbm_bytes_per_s"]  # of each second
    return 100.0 * least_share / (kernel_s / tr["reduced"]["span_s"])  # of each second the ops span

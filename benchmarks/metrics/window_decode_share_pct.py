"""Of the paged-decode kernel's device time in a mixed stack, the share its
WINDOW layers' calls take (`paged_decode_window`) beside the full layers'
(`paged_decode`): K-EXAONE's cut makes ten calls an iteration that read at
most 128 keys a lane (bound by their launch) against three that stream a
lane's whole context. Lower = less of decode attention is launch overhead."""
import trace as T

WINDOW = r"paged_decode_window"  # the window layers' pallas_call (ops/attention.py)
BOTH = r"paged_decode"  # either kind's

META = {"name": "window_decode_share_pct", "unit": "%", "better": "lower", "source": "device_trace",
        "layer": "Kernels (ops/attention.py _paged_decode_kernel)", "moves": "tokens_per_s",
        "workloads": ["k-exaone-236b-a23b.mixed-closed"]}


def read(run):
    reduced = run["trace"]["reduced"]
    window_s, n_window = T.op_seconds(reduced, WINDOW)
    both_s, n_both = T.op_seconds(reduced, BOTH)
    if not n_window or n_both <= n_window or both_s <= 0:
        return None  # not a mixed stack's trace: one of the two kinds is absent
    return 100.0 * window_s / both_s

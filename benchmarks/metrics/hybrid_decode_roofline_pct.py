"""The paged-decode kernel's share of its roofline in a MIXED stack, bound by
HBM bandwidth, over both kinds of call: `latent_decode_roofline_pct`'s reader
called as a module (no formula copied), its kernel pattern set to
`paged_decode`, which matches the full layers' calls (`paged_decode`) and the
window layers' (`paged_decode_window`). The numerator is the family's
`decode_kv_read_bytes` of the sub-window's decode tokens: each its whole
context on a full layer and at most the window on a windowed one (4,096 B x
(3 x context + 10 x min(context, 128)) for K-EXAONE's cut, from the benchmark's
own records) over the peak bandwidth; the denominator the summed device time of
both names."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "accepted_latent_decode_roofline_pct", os.path.join(os.path.dirname(__file__), "latent_decode_roofline_pct.py"))
_accepted = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_accepted)
_accepted.KERNEL = r"paged_decode"  # both pallas_calls' names (ops/attention.py); this copy of the module is ours

META = {"name": "hybrid_decode_roofline_pct", "unit": "%", "better": "higher", "source": "device_trace",
        "layer": "Kernels (ops/attention.py _paged_decode_kernel)", "moves": "tokens_per_s",
        "workloads": ["k-exaone-236b-a23b.mixed-closed"]}

read = _accepted.read

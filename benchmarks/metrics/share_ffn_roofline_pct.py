"""The held experts' matmuls' share of their roofline where a chip holds a
SHARE of a layer's experts: `moe_ffn_roofline_pct`'s reader, called as a module
(no formula copied; that file lists its cells and only a `benchmark` PR may
edit it). The step records' `moe_pairs` are then the pairs computed HERE and
`moe_touched` counts the held experts that were given a row, so the family's
`moe_least_seconds` is the held experts' stream and FLOPs, over the summed
device time of the grouped-matmul kernel."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "accepted_moe_ffn_roofline_pct", os.path.join(os.path.dirname(__file__), "moe_ffn_roofline_pct.py"))
_accepted = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_accepted)

META = {"name": "share_ffn_roofline_pct", "unit": "%", "better": "higher", "source": "device_trace",
        "layer": "Kernels (ops/grouped.py _gmm_kernel)", "moves": "tokens_per_s",
        "workloads": ["k-exaone-236b-a23b.mixed-closed"]}

read = _accepted.read

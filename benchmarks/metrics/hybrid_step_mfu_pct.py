"""The whole step's share of the chip's peak in a cell of the hybrid_moe family:
`step_rows_mfu_pct`'s reader, called as a module (no formula copied), under a
name of its own because that metric's file lists its cells and only a
`benchmark` PR may edit it (which then folds the lists of the now three names
of this one reader into one). The numerator is the family's
`least_step_seconds` over the rows and decode tokens of the step records the
trace holds: a token's OWN work (its projections, attention over at most 128
keys on a window layer and over its whole context on a full one, of its 8
routed pairs those computed HERE, the shared expert, router, the sliced head)."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "accepted_step_rows_mfu_pct", os.path.join(os.path.dirname(__file__), "step_rows_mfu_pct.py"))
_accepted = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_accepted)

META = {"name": "hybrid_step_mfu_pct", "unit": "%", "better": "higher", "source": "device_trace",
        "layer": "Device, whole step", "moves": "tokens_per_s",
        "workloads": ["k-exaone-236b-a23b.mixed-closed"]}

read = _accepted.read

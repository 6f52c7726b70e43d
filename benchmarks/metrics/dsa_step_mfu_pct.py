"""The whole step's share of the chip's peak in a cell of the dsa_moe family:
`step_rows_mfu_pct`'s reader, called as a module (no formula copied), under a
name of its own because that metric's file lists its cells and only a
`benchmark` PR may edit it. The numerator is the family's
`least_step_seconds` over the rows and decode tokens of the step records the
trace holds: a token's OWN work (the latent projections and the indexer's,
the indexer over its whole context, attention over the min(2,048, context)
rows it chose, its routed pairs held here and the shared expert, router,
head), so rows the program computes beside them count nothing."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "accepted_step_rows_mfu_pct", os.path.join(os.path.dirname(__file__), "step_rows_mfu_pct.py"))
_accepted = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_accepted)

META = {"name": "dsa_step_mfu_pct", "unit": "%", "better": "higher", "source": "device_trace",
        "layer": "Device, whole step", "moves": "tokens_per_s",
        "workloads": ["deepseek-v3.2.docs-closed"]}

read = _accepted.read

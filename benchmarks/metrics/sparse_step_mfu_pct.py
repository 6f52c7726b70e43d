"""The whole step's share of the chip's peak in a cell of the latent_moe family:
`step_rows_mfu_pct`'s reader, called as a module (no formula copied), under a
name of its own because that metric's file lists its cells and only a
`benchmark` PR may edit it (which then folds the two lists into one). The
numerator is the family's `least_step_seconds` over the rows and decode
tokens of the step records the trace holds: a token's OWN work (the latent
projections, absorbed attention by context, its 4 routed experts and the
shared one, router, head), so experts a token was not routed to count nothing."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "accepted_step_rows_mfu_pct", os.path.join(os.path.dirname(__file__), "step_rows_mfu_pct.py"))
_accepted = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_accepted)

META = {"name": "sparse_step_mfu_pct", "unit": "%", "better": "higher", "source": "device_trace",
        "layer": "Device, whole step", "moves": "tokens_per_s",
        "workloads": ["glm-4.7-flash.think-closed"]}

read = _accepted.read

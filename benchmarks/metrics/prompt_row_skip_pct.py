"""How often a program went out without a row of a request whose prompt was
waiting: over the requests whose first token fell in the window, 100 x the sum
of (decode-class programs, `step` `chunk` `verify`, dispatched from its first
row's program to its last row's, less those that carried its rows) over the sum
of those programs. A wave's prefill carries a whole prompt in a program of its
own kind, which counts as the one program of its stretch. The join is
`first_row_wait_ms_mean`'s (`joined`, called as a module)."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "request_timeline_join", os.path.join(os.path.dirname(__file__), "first_row_wait_ms_mean.py"))
_timeline = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_timeline)

META = {"name": "prompt_row_skip_pct", "unit": "%", "better": "lower", "source": "program_span",
        "layer": "Scheduler (step packing)", "moves": "ttft_ms_mean",
        "workloads": ["mistral-7b.long-closed", "qwen2-7b.reason-closed", "glm-4.7-flash.think-closed",
                      "k-exaone-236b-a23b.mixed-closed", "olmo-hybrid-7b.chat-closed"]}


def read(run):
    got = _timeline.joined(run)
    if got is None:
        return None
    programs = [(max(n, r["row_steps"]), r["row_steps"]) for r, _a, _b, n in got]
    return 100.0 * sum(n - carried for n, carried in programs) / sum(n for n, _c in programs)

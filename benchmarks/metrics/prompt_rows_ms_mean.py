"""The dispatch of the program that carried the request's first prompt row ->
the dispatch of the one whose row finished its prompt, mean over the requests
whose first token fell in the window: the stretch over which its rows went out.
A one-row prompt reads 0. The join is `first_row_wait_ms_mean`'s (`joined`,
called as a module)."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "request_timeline_join", os.path.join(os.path.dirname(__file__), "first_row_wait_ms_mean.py"))
_timeline = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_timeline)

META = {"name": "prompt_rows_ms_mean", "unit": "ms", "better": "lower", "source": "program_span",
        "layer": "Scheduler (step packing)", "moves": "ttft_ms_mean",
        "workloads": ["mistral-7b.long-closed", "qwen2-7b.reason-closed", "glm-4.7-flash.think-closed",
                      "k-exaone-236b-a23b.mixed-closed", "olmo-hybrid-7b.chat-closed"]}


def read(run):
    got = _timeline.joined(run)
    return None if got is None else 1e3 * sum(b["t_dispatch"] - a["t_dispatch"] for _r, a, b, _n in got) / len(got)

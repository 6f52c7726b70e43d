"""Share of the traced window in which the device was idle BETWEEN two program
executions: the next program had not been dispatched, or not reached the device.
The host's to shorten (hostspans.py names the span each engine thread was in)."""
import hostspans

META = {"name": "idle_between_programs_pct", "unit": "%", "better": "lower", "source": "program_span",
        "layer": "Scheduler (dispatch/collect pipeline)", "moves": "tokens_per_s",
        "workloads": ["qwen2-7b.reason-closed", "mistral-7b.long-closed"]}


def read(run):
    return hostspans.idle_pct(run, "between")

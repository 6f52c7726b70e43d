"""Share of the traced window in which the device was idle at the EDGES of the
traced stretch: before the first or after the last whole program execution, and
what the host's start_trace..stop_trace stretch holds beyond the operations' own
span. What the profiler itself costs the window; with the other two positions it
sums to device_idle_pct."""
import hostspans

META = {"name": "idle_edge_pct", "unit": "%", "better": "lower", "source": "program_span",
        "layer": "Device", "moves": "tokens_per_s",
        "workloads": ["qwen2-7b.reason-closed", "mistral-7b.long-closed"]}


def read(run):
    return hostspans.idle_pct(run, "edge")

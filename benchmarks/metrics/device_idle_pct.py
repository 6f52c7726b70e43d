"""Share of the traced sub-window in which no operation ran on the device.
The window is the host's `start_trace` -> `stop_trace` stretch (or the ops'
own span where the trace holds more), so idle time at its edges counts; a
loop's own event is not an operation here, only what runs inside it."""

META = {"name": "device_idle_pct", "unit": "%", "better": "lower", "source": "device_trace",
        "layer": "Device", "moves": "tokens_per_s",
        "workloads": ["qwen2-7b.reason-closed", "mistral-7b.long-closed"]}


def read(run):
    red = run["trace"]["reduced"]
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])

"""Share of the traced window in which the device was idle INSIDE a program
execution (between two of its operations on the "XLA Modules" line): the program
had started and waited, for an input or for the device's own scheduling."""
import hostspans

META = {"name": "idle_in_program_pct", "unit": "%", "better": "lower", "source": "program_span",
        "layer": "Device", "moves": "tokens_per_s",
        "workloads": ["qwen2-7b.reason-closed", "mistral-7b.long-closed"]}


def read(run):
    return hostspans.idle_pct(run, "in_program")

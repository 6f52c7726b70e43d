"""Device time of one decode iteration: each execution of the decode-only
program runs `decode_chunk` iterations."""
import trace as T

PROGRAM = r"^jit__chunk"  # llm.decode_chunk8: the jitted function is `_chunk`

META = {"name": "decode_step_device_ms", "unit": "ms", "better": "lower", "source": "device_trace",
        "layer": "Model step (llm.decode_chunk8: decode_chunk_paged / decode_chunk)",
        "moves": "tpot_ms_mean", "workloads": ["qwen2-7b.reason-closed"]}


def read(run):
    runs = T.module_runs(run["trace"]["reduced"], PROGRAM)
    if not runs:
        return None
    return sum(runs) / len(runs) / run["engine"]["decode_chunk"] * 1e3

"""Device time of one execution of the fused prefill+decode programs
(llm.step_p*_d8), mean over the traced sub-window. A prompt advances one
64-token row per fused step, so this sets TTFT; the decoding lanes' next 8
tokens wait for the same step, so TPOT feels it too."""
import trace as T

PROGRAM = r"^jit__step"  # every llm.step_p<chunk>_d<k> jits a function named `_step`

META = {"name": "fused_step_device_ms", "unit": "ms", "better": "lower", "source": "device_trace",
        "layer": "Model step (llm.step_p*_d8: prefill_append + decode chunk)",
        "moves": "ttft_ms_mean", "workloads": ["qwen2-7b.reason-closed", "mistral-7b.long-closed"]}


def read(run):
    runs = T.module_runs(run["trace"]["reduced"], PROGRAM)
    return sum(runs) / len(runs) * 1e3 if runs else None

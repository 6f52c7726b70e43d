"""The whole step's share of the chip's peak: the least compute time of
every token the sub-window processed (prefill matmuls at the int8 peak,
decode matmuls, attention and the head at the bf16 peak) over the traced
device window and the chips.

The denominator is the trace's. The numerator is counted from the
benchmark's own request records: decode tokens by their yield stamps, prompt
tokens spread evenly between a request's admission and its first token
(`stats.prefill_contexts`), because the program does not say which rows a
step carried (PERF.md, Open questions)."""
import stats as S

META = {"name": "step_mfu_pct", "unit": "%", "better": "higher", "source": "device_trace",
        "layer": "Device, whole step", "moves": "tokens_per_s",
        "workloads": ["qwen2-7b.reason-closed", "mistral-7b.long-closed"]}


def read(run):
    tr = run["trace"]
    ta, tb = tr["ta"], tr["tb"]
    least = run["family"].least_step_seconds(
        run["model"], run["peaks"],
        prefill_contexts=S.prefill_contexts(run["records"], ta, tb),
        decode_contexts=S.decode_contexts(run["records"], ta, tb),
        prefill_int8=bool(run["config"]["engine"].get("quantize")),
    )
    return 100.0 * least["seconds"] / (tr["reduced"]["window_s"] * run["chips"])

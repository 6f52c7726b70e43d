"""Tokens packed into the fused prefill+decode steps of the window over
steps x step_token_budget. The counter (llm.py `_stat_step_tokens`) adds, for
every fused step, its prompt tokens plus decode_chunk x decoding lanes; decode
rides every step whatever the budget, so this passes 100 when the lanes alone
hold more than the budget. Decode-only chunks are not steps."""

META = {"name": "step_fill_pct", "unit": "%", "better": "higher", "source": "program_counter",
        "layer": "Scheduler (step packing)", "moves": "tokens_per_s",
        "workloads": ["qwen2-7b.reason-closed", "mistral-7b.long-closed"]}


def read(run):
    a, b = run["stats0"], run["stats1"]
    steps = b["steps"] - a["steps"]
    if steps <= 0:
        return None
    return 100.0 * (b["step_tokens"] - a["step_tokens"]) / (steps * b["step_token_budget"])

"""Of the steps dispatched in the window, the share that went out without their
decode chunk (`llm.step_p<chunk>_d0`): the engine's `stats()["steps_without_decode"]`
over `stats()["steps"]`, both at the window's two ends. The scheduler leaves the
chunk out of a step in which no lane decodes and no row finishes its prompt, so
this says how often that engages: three steps in ten where long prompts keep
most lanes in prefill, none where a lane always decodes. An engine without the
counter (one from before PR 32) reads nothing."""

META = {"name": "steps_without_decode_pct", "unit": "%", "better": "higher", "source": "program_counter",
        "layer": "Scheduler (step packing)", "moves": "tokens_per_s",
        "workloads": ["mistral-7b.long-closed", "qwen2-7b.reason-closed", "glm-4.7-flash.think-closed"]}


def read(run):
    a, b = run["stats0"] or {}, run["stats1"] or {}
    if "steps_without_decode" not in a or "steps_without_decode" not in b:
        return None
    steps = b["steps"] - a["steps"]
    if steps <= 0:
        return None
    return 100.0 * (b["steps_without_decode"] - a["steps_without_decode"]) / steps

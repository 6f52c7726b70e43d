"""Of the routed experts' weights, the share a layer call had to stream: experts
that were given at least one row over experts times layer calls, from the
engine's `stats()["moe"]` at the window's two ends. The routing's own number
(lower = fewer bytes a step), there so that the expert stream's bytes can be
read beside the step's time. An engine that reports no `moe` reads nothing."""

META = {"name": "moe_experts_touched_pct", "unit": "%", "better": "lower", "source": "program_counter",
        "layer": "Routed FFN (models/moe.py routed_ffn)", "moves": "tokens_per_s",
        "workloads": ["glm-4.7-flash.think-closed"]}


def read(run):
    a, b = (run["stats0"] or {}).get("moe"), (run["stats1"] or {}).get("moe")
    if not a or not b:
        return None
    calls = b["layer_calls"] - a["layer_calls"]
    experts = len(b["tokens_per_expert"])
    if calls <= 0 or not experts:
        return None
    return 100.0 * (b["touched"] - a["touched"]) / (experts * calls)

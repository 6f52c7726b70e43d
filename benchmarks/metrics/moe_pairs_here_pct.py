"""Of the (token, expert) pairs the routers chose in the window, the share
whose expert is held HERE and was computed: the engine's `stats()["moe"]`
`pairs` over `pairs_routed` at the window's two ends. 100 x held / routed
experts where routing is even (16 of 128: 12.5); above it this chip's experts
are the busier ones and its step the longer. An engine that reports no
`pairs_routed` (it holds every expert, or none) reads nothing."""

META = {"name": "moe_pairs_here_pct", "unit": "%", "better": "lower", "source": "program_counter",
        "layer": "Routed FFN (models/moe.py routed_ffn)", "moves": "tokens_per_s",
        "workloads": ["k-exaone-236b-a23b.mixed-closed"]}


def read(run):
    a, b = (run["stats0"] or {}).get("moe"), (run["stats1"] or {}).get("moe")
    if not a or not b or "pairs_routed" not in a or "pairs_routed" not in b:
        return None
    routed = b["pairs_routed"] - a["pairs_routed"]
    if routed <= 0:
        return None
    return 100.0 * (b["pairs"] - a["pairs"]) / routed

"""The hybrid_moe family (families/hybrid_moe.py): the plain reference against
the program's forward pass at the twin with every mechanism load-bearing, the
gap of the reference's own choice zero and the int4 control failing, the
share's weights being the uncut layer's at their indices, and the cell's
files held to each other. The comparisons through both pools, the shares
adding up, the counts against numbers worked out by hand and the cell's
rehearsal are in tier-1: tests/test_mixed_stack.py, tests/test_mixed_cell_rehearsal.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
import run as R
import traffic

import hybrid_moe_weights as W

SEED = 2**31 + 33
CELL = "k-exaone-236b-a23b.mixed-closed"
CONFIG = traffic.load(f"{R.HERE}/configs/k-exaone-236b-a23b.json")
family = R.load_family(CONFIG)


def test_reference_equals_the_programs_forward(monkeypatch):
    import gofr_tpu.models.transformer as TM

    model = CONFIG["rehearsal"]["model"]
    toks = np.random.default_rng(0).integers(1, model["vocab_size"], (2, 40)).astype(np.int32)
    monkeypatch.setattr(TM, "qmm_a8", TM.qmm)
    pos = jnp.broadcast_to(jnp.arange(40), toks.shape)
    got, _ = TM.transformer_forward(family.program_params(model, SEED), family.program_config(model),
                                    jnp.asarray(toks), pos)
    ref = family.forward_logits(model, SEED, toks)
    assert float(jnp.max(jnp.abs(got - ref))) < 2e-4 and float(jnp.std(ref)) > 0.5
    # every mechanism is load-bearing: without it the logits move by far more than the tolerance
    for change in ({"routed_scaling_factor": 1.0}, {"norm_topk_prob": False}, {"first_expert_held": 4},
                   {"sliding_windows": [0] * 12}, {"sliding_windows": [8] * 12},
                   {"rope_parameters": {"rope_theta": 10000, "rope_type": "default"}}, {"first_k_dense_replace": 2}):
        other = family.forward_logits({**model, **change}, SEED, toks)
        assert float(jnp.max(jnp.abs(other - ref))) > 1e-2, change


def test_gaps_of_the_references_own_choice_are_zero_and_the_control_fails():
    model = CONFIG["rehearsal"]["model"]
    prompt, n = np.random.default_rng(1).integers(1, 512, 24).tolist(), 12
    seq = list(prompt)
    for _ in range(n):
        lg = family.forward_logits(model, SEED, np.asarray([seq + [0] * (40 - len(seq))], np.int32))
        seq.append(int(jnp.argmax(lg[0, len(seq) - 1])))
    served = seq[len(prompt):]
    res = family.gaps(model, SEED, [(prompt, served)], 64, control=True)
    assert len(res["gap"]) == n and max(res["gap"]) == 0.0 and all(res["agree"])
    assert max(res["token_control_gap"]) > 0.05  # int4 weights put another token first somewhere
    assert max(res["control_gap"]) == pytest.approx(np.mean(res["token_control_gap"]))  # one window: the mean
    # the second control: a reference that leaves its held experts' part out puts other tokens first too
    assert len(res["no_routed_gap"]) == n and max(res["token_no_routed_gap"]) > 0.05
    assert max(res["no_routed_gap"]) == pytest.approx(np.mean(res["token_no_routed_gap"]))


def test_the_correction_bias_evens_the_experts_load():
    """`even_bias` is the bias a balanced training leaves: over FRESH hidden
    states of no preferred direction (N(0, g^2), not the draws it was fitted
    on) every expert of the twin's router is chosen equally often, to the
    sampling floor (4 of 16 chosen over 65,536 rows: 0.8% an expert, as much
    again from the fit's own draws); a zero bias leaves what the router's
    columns' lengths make (d = 64: ~7%), and the N(0, 0.01) draw this replaced
    more. The same key gives the same bias."""
    model = {k: v for k, v in CONFIG["rehearsal"]["model"].items() if not isinstance(v, (list, dict))}
    leaves = W.layer_leaves(model, W.layer_keys(W.base_key(SEED), model)[2], True)
    again = W.layer_leaves(model, W.layer_keys(W.base_key(SEED), model)[2], True)
    assert bool(jnp.all(leaves["router_bias"] == again["router_bias"])) and abs(float(leaves["router_bias"].mean())) < 1e-6
    g = 1.0 + leaves["mlp_norm"].astype(jnp.float32)
    h = jax.random.normal(jax.random.PRNGKey(7), (65536, g.shape[0]), jnp.float32) * g
    scores = jax.nn.sigmoid(jnp.matmul(h, leaves["w_router"], precision="highest"))

    def spread(bias):
        chosen = np.asarray(jax.lax.top_k(scores + bias, model["num_experts_per_tok"])[1])
        counts = np.bincount(chosen.reshape(-1), minlength=16)
        return float(counts.std() / counts.mean())

    even, zero = spread(leaves["router_bias"]), spread(0.0)
    seeded = spread(0.01 * jax.random.normal(jax.random.PRNGKey(8), (16,), jnp.float32))
    assert even < 0.02 and zero > 3 * even and seeded > 3 * even, (even, zero, seeded)


def test_a_share_holds_the_uncut_layers_arrays_at_its_indices():
    model = {k: v for k, v in CONFIG["rehearsal"]["model"].items() if not isinstance(v, (list, dict))}
    key = W.layer_keys(W.base_key(SEED), model)[1]
    uncut = W.layer_leaves({**model, "num_experts": 16, "first_expert_held": 0}, key, True)
    share = W.layer_leaves({**model, "num_experts": 4, "first_expert_held": 8}, key, True)
    for name in ("w_gate", "w_up", "w_down"):
        assert share[name].shape[0] == 4 and bool(jnp.all(share[name] == uncut[name][8:12]))
    for name in ("w_router", "router_bias", "wq", "ws_gate", "q_norm"):
        assert bool(jnp.all(share[name] == uncut[name]))
    assert not bool(jnp.all(uncut["w_gate"][0] == uncut["w_gate"][1]))  # every expert drawn alone


def test_a_token_enters_at_unit_variance_on_both_sides():
    """`embed_scale`: the table's one scale is 1 / 73 in the program's leaf and in the
    reference's rows alike (a matmul weight's scale let a token enter at 1 / sqrt(d) an
    element, under what layer 0's attention adds: a sequence then kept one direction
    and favourite experts, PERF.md section 6, PR 33)."""
    import hybrid_moe_reference as REF

    model = CONFIG["rehearsal"]["model"]
    ids = jnp.arange(1, 129, dtype=jnp.int32)
    emb = family.program_params(model, SEED)["embed"]
    got = emb.q[ids].astype(jnp.float32) * emb.s.astype(jnp.float32)
    ref = REF._embed_rows(REF._frozen(model), W.base_key(SEED), ids, False)
    assert bool(jnp.all(got == ref)) and 0.95 < float(jnp.std(ref)) < 1.05
    assert float(emb.s.reshape(-1)[0]) == float(W.embed_scale(model)) == float(W.scale_of(1, W.dtype_of(model)))
    assert "embedding_scale" in CONFIG["assumed"]


def test_the_files_of_the_cell_hold_each_other():
    spec = traffic.load(f"{R.REPO}/BENCHMARK.json")
    entry = next(c for c in spec["configs"] if c["name"] == "k-exaone-236b-a23b")
    assert entry["reduced"] == CONFIG["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert CONFIG["published"] == {"num_hidden_layers": 48, "num_experts": 128, "vocab_size": 153600}
    model = CONFIG["model"]
    assert (model["num_hidden_layers"], model["num_experts"], model["num_experts_routed"], model["vocab_size"]) == (
        13, 16, 128, 19200)
    assert W.windows(model) == (128, 128, 128, 0) * 3 + (128,)
    for key in ("norm_placement", "qk_norm", "full_layers_rotation", "router_bias", "rope", "num_nextn_predict_layers"):
        assert key in CONFIG["assumed"]
    # the catalog's keys stand at the top level as run (model-configs guide), equal to the model group's
    for key, value in model.items():
        if key not in ("num_experts_routed", "first_expert_held", "dtype"):
            assert CONFIG[key] == value, key
    workload = traffic.load(f"{R.HERE}/workloads/{CELL}.json")
    plan = traffic.Plan(workload, 1, model["vocab_size"])
    longest = max(r.prompt_len + r.output_len for r in plan.distinct())
    assert longest <= CONFIG["engine"]["max_seq_len"] == 8192 and plan.clients == CONFIG["engine"]["slots"] == 16
    cells = {m["name"]: m["workloads"] for m in spec["per_layer"] if CELL in m.get("workloads", [])}
    assert set(cells) == {"hybrid_step_mfu_pct", "hybrid_decode_roofline_pct", "window_decode_share_pct",
                          "share_ffn_roofline_pct", "window_kv_held_pct", "moe_pairs_here_pct"}

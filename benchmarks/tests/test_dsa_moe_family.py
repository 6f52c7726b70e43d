"""The dsa_moe family (families/dsa_moe.py): the plain reference against the
program's forward pass at the twin with every mechanism load-bearing (the
indexer's choice, YaRN, the group limit, the share), the gap of the
reference's own choice zero and the int4 control failing, the counts against
the numbers worked out by hand for DeepSeek-V3.2's cut, and the cell's files
held to each other. The paged programs against the reference, the radix hit,
the shares adding up and the refusals are in tier-1: tests/test_dsa_moe.py."""
import jax.numpy as jnp
import numpy as np
import pytest
import run as R
import traffic

import dsa_moe_costs as C
import dsa_moe_weights as W

SEED = 2**31 + 40
CELL = "deepseek-v3.2.docs-closed"
CONFIG = traffic.load(f"{R.HERE}/configs/deepseek-v3.2.json")
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
    for change in ({"index_topk": 40}, {"rope_scaling": None}, {"n_group": 1, "topk_group": 1},
                   {"first_expert_held": 4}, {"routed_scaling_factor": 1.0}, {"first_k_dense_replace": 2}):
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
    assert max(res["token_control_gap"]) > 0.05
    assert max(res["control_gap"]) == pytest.approx(np.mean(res["token_control_gap"]))


def test_a_shared_document_is_passed_once_and_gives_the_whole_pass():
    """Two requests that open with one 70-token document: the reference passes
    its first 64 tokens (whole query blocks) once and each request's rest
    over them, and gets the hidden states of the whole pass, positions
    (rope, the causal mask, the indexer's top-k) included."""
    import dsa_moe_reference as REF

    model = CONFIG["rehearsal"]["model"]
    rng = np.random.default_rng(3)
    doc = rng.integers(1, model["vocab_size"], 70)
    seqs = [np.concatenate([doc, rng.integers(1, model["vocab_size"], n)]) for n in (30, 41)]
    toks = np.zeros((2, 128), np.int32)
    for i, q in enumerate(seqs):
        toks[i, : len(q)] = q
    assert REF.shared_head(seqs, 99) == 64 and REF.shared_head(seqs, 63) == 0
    whole = REF.hidden_states(model, SEED, toks)
    part = REF.hidden_states(model, SEED, toks, shared=64)
    assert part.shape == (2, 64, model["hidden_size"])
    assert float(jnp.max(jnp.abs(part - whole[:, 64:]))) < 1e-4 * float(jnp.max(jnp.abs(whole)))


def test_the_costs_are_the_hand_counts():
    """DeepSeek-V3.2 as cut: MLA 187.1 M a layer, the indexer 14.0 M, an
    expert 44.04 M, 0.5 of a token's 8 pairs here; a cache row 1,536 B, its
    index key 256 B, a chosen row's latent and rope 1,280 B; 7.53 GB of
    weights."""
    m = CONFIG["model"]
    assert C.attention_matmul_params(m) == 187_105_280
    assert C.indexer_params(m) == 1536 * 64 * 128 + 7168 * 128 + 7168 * 64 == 13_959_168
    assert C.expert_params(m) == 44_040_192 and C.pairs_here_per_token(m) == 0.5
    assert C.row_bytes(m) == 1536 and C.index_key_bytes(m) == 256 and C.chosen_row_bytes(m) == 1280
    assert 7.50e9 < C.weight_bytes(m) < 7.56e9
    # one decode token at 13,312 tokens: the indexer reads every row's key, attention 2,048 rows, in 8 layers
    assert C.index_read_bytes(m, [13312]) == 8 * 256 * 13312
    assert C.sparse_read_bytes(m, [13312]) == 8 * 1280 * 2048 and C.sparse_read_bytes(m, [100]) == 8 * 1280 * 100
    assert C.decode_kv_read_bytes(m, [13312]) == 8 * (256 * 13312 + 1280 * 2048)
    assert C.attention_flops(m, 13312) == 8 * (2 * 64 * 128 * 13312 + 2 * 128 * (576 + 512) * 2048)
    assert C.attention_flops(m, 1000) == 8 * (2 * 64 * 128 * 1000 + 2 * 128 * (576 + 512) * 1000)


def test_a_share_holds_the_uncut_layers_arrays_at_its_indices():
    model = {k: v for k, v in CONFIG["rehearsal"]["model"].items() if not isinstance(v, (list, dict))}
    key = W.layer_keys(W.base_key(SEED), model)[1]
    uncut = W.layer_leaves({**model, "n_routed_experts": 8}, key, True)
    share = W.layer_leaves({**model, "first_expert_held": 4}, key, True)
    for name in ("w_gate", "w_up", "w_down"):
        assert share[name].shape[0] == 4 and bool(jnp.all(share[name] == uncut[name][4:8]))
    for name in ("w_router", "router_bias", "wq_b", "wq_idx", "k_idx_norm", "ws_gate"):
        assert bool(jnp.all(share[name] == uncut[name]))


def test_the_files_of_the_cell_hold_each_other():
    spec = traffic.load(f"{R.REPO}/BENCHMARK.json")
    entry = next(c for c in spec["configs"] if c["name"] == "deepseek-v3.2")
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts", "vocab_size"]
    assert CONFIG["published"] == {"num_hidden_layers": 61, "first_k_dense_replace": 3, "n_routed_experts": 256,
                                   "vocab_size": 129280}
    model = CONFIG["model"]
    assert (model["num_hidden_layers"], model["first_k_dense_replace"], model["n_routed_experts"],
            model["n_routed_experts_routed"], model["vocab_size"]) == (8, 1, 16, 256, 16160)
    assert (model["index_topk"], model["n_group"], model["topk_group"], model["num_experts_per_tok"]) == (2048, 8, 4, 8)
    for key in ("indexer_precision", "router_bias", "rope", "num_nextn_predict_layers", "embedding_scale"):
        assert key in CONFIG["assumed"]
    for key, value in model.items():  # the catalog's keys stand at the top level as run
        if key not in ("n_routed_experts_routed", "first_expert_held", "dtype"):
            assert CONFIG[key] == value, key
    workload = traffic.load(f"{R.HERE}/workloads/{CELL}.json")
    plan = traffic.Plan(workload, 1, model["vocab_size"])
    longest = max(r.prompt_len + r.output_len for r in plan.distinct())
    assert longest <= CONFIG["engine"]["max_seq_len"] == 14336 and plan.clients == CONFIG["engine"]["slots"] == 16
    cells = {m["name"] for m in spec["per_layer"] if CELL in m.get("workloads", [])}
    assert cells == {"dsa_step_mfu_pct", "dsa_index_roofline_pct", "dsa_decode_roofline_pct", "dsa_rows_read_pct",
                     "prompt_cached_pct"}

"""The latent_moe family (families/latent_moe.py) and its cell: the plain
reference against the program's forward pass at the twin, the int4 control
failing, each count against numbers worked out by hand (GLM-4.7-Flash's
published sizes), and the cell's rehearsal end to end with its four metrics."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import run as R
import traffic

SEED = 2**31 + 29
CELL = "glm-4.7-flash.think-closed"
CONFIG = traffic.load(f"{R.HERE}/configs/glm-4.7-flash.json")
family = R.load_family(CONFIG)
ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}
PEAKS = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9}


def test_reference_equals_the_programs_forward(monkeypatch):
    """Expanded float32 reference against the program's absorbed attention and
    grouped experts, exact activations on both sides (the W8A8 prompt path is
    judged by the gap, not here): float32 rounding apart, the same logits."""
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
    for change in ({"routed_scaling_factor": 1.0}, {"norm_topk_prob": False}, {"n_shared_experts": 2},
                   {"rope_theta": 10000}, {"first_k_dense_replace": 2}):
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


def _served_by_the_program(model, prompt, n, monkeypatch, fault=None):
    """`n` greedy tokens of the program's own forward pass (exact activations:
    the twin is float32), with a fault patched into its routed FFN or none."""
    import gofr_tpu.models.moe as MOE
    import gofr_tpu.models.transformer as TM
    import gofr_tpu.ops.grouped as G

    monkeypatch.setattr(TM, "qmm_a8", TM.qmm)
    if fault == "wrong tile -> expert map":  # every row tile multiplies the next expert's weights
        gmm, E = G.grouped_matmul, model["n_routed_experts"]
        monkeypatch.setattr(G, "grouped_matmul", lambda x, w, *, tile_expert, padded_counts, **kw: gmm(
            x, w, tile_expert=(tile_expert + 1) % E, padded_counts=jnp.roll(padded_counts, 1), **kw))
    elif fault == "a dropped pair":  # every token's last choice never reaches its expert's output
        route = MOE.route

        def dropping(cfg, h, lp):
            experts, weights = route(cfg, h, lp)
            return experts, weights.at[:, -1].set(0.0)

        monkeypatch.setattr(MOE, "route", dropping)
    cfg, params = family.program_config(model), family.program_params(model, SEED)
    width = len(prompt) + n
    pos = jnp.arange(width)[None]
    forward = jax.jit(lambda toks: TM.transformer_forward(params, cfg, toks, pos)[0])
    seq = list(prompt)
    for _ in range(n):
        lg = forward(jnp.asarray([seq + [0] * (width - len(seq))], jnp.int32))
        seq.append(int(jnp.argmax(lg[0, len(seq) - 1])))
    monkeypatch.undo()
    return seq[len(prompt):]


@pytest.mark.parametrize("fault", [None, "wrong tile -> expert map", "a dropped pair"])
def test_a_fault_in_the_routed_ffn_reads_over_the_limit(fault, monkeypatch):
    """What the cell's check is there for, at the twin on the CPU: the sound
    program reads 0 (exact activations), a routing fault reads over the
    rehearsal's limit by the mean gap alone."""
    model, cell = CONFIG["rehearsal"]["model"], traffic.load(f"{R.HERE}/workloads/{CELL}.json")
    prompt = np.random.default_rng(2).integers(1, 512, 24).tolist()
    served = _served_by_the_program(model, prompt, 40, monkeypatch, fault)
    res = family.gaps(model, SEED, [(prompt, served)], 64)
    limit = cell["rehearsal"]["check"]["gap_limit"]
    if fault is None:
        assert max(res["gap"]) < 1e-3 and all(res["agree"])
    else:
        assert max(res["gap"]) > limit, (fault, max(res["gap"]))


def test_a_gap_is_the_mean_of_a_window_of_served_tokens():
    import latent_moe_reference as REF

    assert REF.window_means([1, 2, 3, 6], 2).tolist() == [1.5, 1.5, 2.5, 4.5]
    assert REF.window_means([1, 2, 3], 256).tolist() == [2, 2, 2]  # fewer than a window: one mean
    one_bad = np.zeros(1000); one_bad[500] = 5.0  # a single token's gap is diluted, a stretch's is not
    assert REF.window_means(one_bad).max() == pytest.approx(5 / 256)
    stretch = np.zeros(1000); stretch[300:556] = 1.0
    assert REF.window_means(stretch).max() == 1.0


def test_a_model_the_family_does_not_know_is_refused():
    model = CONFIG["model"]
    for change, words in (({"n_group": 8}, "ONE group"), ({"rope_scaling": {"type": "yarn"}}, "rope scaling"),
                          ({"topk_method": "greedy"}, "topk_method")):
        with pytest.raises(ValueError, match=words):
            family.program_config({**model, **change})


def test_counts_against_numbers_worked_out_by_hand():
    import latent_moe_costs as C

    m = CONFIG["model"]
    assert C.attention_matmul_params(m) == 2048 * 768 + 768 * 5120 + 2048 * 576 + 512 * 8960 + 5120 * 2048 == 21_757_952
    assert C.expert_params(m) == 3 * 2048 * 1536 == 9_437_184
    # a token's own work in an expert layer: attention, 4 routed + 1 shared expert, the router's 64 columns
    assert C.layer_matmul_params(m, True) == 21_757_952 + 5 * 9_437_184 + 2048 * 64 == 69_074_944
    assert C.layer_matmul_params(m, False) == 21_757_952 + 3 * 2048 * 10240 == 84_672_512
    assert C.body_matmul_flops_per_token(m) == 2 * (84_672_512 + 12 * 69_074_944)
    assert C.head_flops_per_logit_row(m) == 2 * 2048 * 154_880
    assert C.attention_flops(m, 1) == 13 * 20 * (576 + 512) * 2 == 13 * 43_520
    assert family.decode_kv_read_bytes(m, [1000, 24]) == 1024 * 13 * 1152
    # what the chip holds at int8: 12 x 635 MB + 85 MB + 634 MB of tables (+ the float32 routers)
    assert abs(C.weight_bytes(m) - 8.35e9) < 0.03e9
    least = family.least_step_seconds(m, PEAKS, prefill_contexts=[1, 2], decode_contexts=[1000], prefill_int8=True)
    body = C.body_matmul_flops_per_token(m)
    assert least["prefill_matmul_flops"] == 2 * body and least["decode_matmul_flops"] == body + 2 * 2048 * 154_880
    assert least["attention_flops"] == 13 * 43_520 * 1003
    assert least["seconds"] == pytest.approx(2 * body / 393e12 + (body + 634_388_480 + 13 * 43_520 * 1003) / 197e12)
    # 16 lanes x 4 experts in 12 layer calls, 41 experts touched in each: the stream binds
    t = family.moe_least_seconds(m, PEAKS, pairs=64 * 12, touched=41 * 12)
    assert t == pytest.approx(41 * 12 * 9_437_184 / 819e9) and t > 64 * 12 * 2 * 9_437_184 / 197e12
    # the FLOPs bind past 120 rows an expert (9.44 MB / 819 GB/s = 11.5 us = 120 x 2 x 9.44 M / 197 T):
    # 16,384 pairs over the 64 experts of one layer
    assert family.moe_least_seconds(m, PEAKS, pairs=16_384, touched=64) == pytest.approx(16_384 * 2 * 9_437_184 / 197e12)
    assert family.moe_least_seconds(m, PEAKS, pairs=4096, touched=64) == pytest.approx(64 * 9_437_184 / 819e9)


def test_the_weights_are_drawn_alike_on_both_sides():
    """The program's stacked groups hold what the reference draws a layer at a time."""
    import latent_moe_weights as W

    model = CONFIG["rehearsal"]["model"]
    params = family.program_params(model, SEED)
    dense, moe = params["layers"]
    keys = W.layer_keys(W.base_key(SEED), model)
    one = W.layer_leaves(model, keys[2], True)  # the second expert layer
    assert np.array_equal(np.asarray(moe["w_gate"].q[1]), np.asarray(one["w_gate"]))
    assert np.array_equal(np.asarray(moe["w_router"][1]), np.asarray(one["w_router"]))
    assert moe["w_gate"].q.shape == (2, 8, 64, 32) and moe["w_gate"].s.shape == (2, 8, 1, 32)
    assert moe["router_bias"].dtype == jnp.float32 and dense["w_gate"].q.shape == (1, 64, 128)
    assert float(moe["w_down"].s[0, 0, 0, 0]) == pytest.approx(1 / (73 * 32 ** 0.5), rel=1e-6)


def bench(*args):
    p = subprocess.run([sys.executable, os.path.join(R.HERE, "run.py"), *args],
                       capture_output=True, text=True, env=ENV, cwd=R.REPO, timeout=900)
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    return p, (json.loads(lines[-1]) if lines else None)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contracts_last_line(trace):
    p, line = bench("--workload", CELL, "--seed", str(2**31 + 74), "--seconds", "3", "--trace", str(trace),
                    "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    assert line["rehearsal"] is True and line["correct"] is True, p.stderr[-2000:]
    spec = json.load(open(os.path.join(R.REPO, "BENCHMARK.json")))
    group = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in spec[group] if "workloads" not in m or CELL in m["workloads"]}
    if not trace:
        assert set(line["metrics"]) == want
    else:
        # the Mosaic kernels have no CPU stand-in; every other metric of the cell is read
        assert want - {"latent_decode_roofline_pct", "moe_ffn_roofline_pct"} == set(line["metrics"])
        assert 0 < line["metrics"]["moe_experts_touched_pct"]["value"] <= 100
        assert line["metrics"]["sparse_step_mfu_pct"]["value"] > 0


def test_the_int4_control_is_not_correct():
    import prove

    p, line = bench("--workload", CELL, "--seed", str(2**31 + 74), "--seconds", "3", "--trace", "0",
                    "--control", "1", "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    assert line["correct"] is False and prove.as_expected(0, line, control=True)

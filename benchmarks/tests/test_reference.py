"""The plain reference against the program's own forward pass at the tiny
twins on the CPU (bias; window past 8 tokens), the control against the
reference, and the comparison's arithmetic."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
import run as R
import traffic

SEED = 2**31 + 11
family = R.load_family({"family": "dense"})  # found as a run finds it


def twin(name):
    return traffic.load(f"{R.HERE}/configs/{name}.json")["rehearsal"]["model"]


@pytest.fixture(scope="module", params=["qwen2-7b", "mistral-7b"])
def case(request):
    model = twin(request.param)
    toks = np.random.default_rng(0).integers(1, model["vocab_size"], (2, 40)).astype(np.int32)
    return model, toks, family.forward_logits(model, SEED, toks)


def test_reference_equals_the_programs_forward(case, monkeypatch):
    """With exact activations (the program's W8A8 prefill quantises them to
    int8 on the fly, which the reference must NOT imitate) the two agree to
    float32 rounding: the same weights from the seed, bias, RoPE, GQA, window."""
    import gofr_tpu.models.transformer as TM
    from gofr_tpu.models.transformer import transformer_forward

    model, toks, ref = case
    monkeypatch.setattr(TM, "qmm_a8", TM.qmm)
    params = family.program_params(model, SEED)
    pos = jnp.broadcast_to(jnp.arange(toks.shape[1]), toks.shape)
    with jax.default_matmul_precision("highest"):
        got, _ = transformer_forward(params, family.program_config(model), jnp.asarray(toks), pos)
    assert float(jnp.max(jnp.abs(got - ref))) < 1e-4
    assert float(jnp.std(ref)) > 0.5  # logits of unit scale: a gap limit means something


def test_window_and_bias_are_load_bearing(case):
    model, toks, ref = case
    if model.get("sliding_window"):
        other = family.forward_logits({**model, "sliding_window": 0}, SEED, toks)
        assert float(jnp.max(jnp.abs(other[:, :8] - ref[:, :8]))) < 1e-5  # inside the window: equal
        assert float(jnp.max(jnp.abs(other[:, 20:] - ref[:, 20:]))) > 1e-2
    if model.get("qkv_bias"):
        other = family.forward_logits({**model, "qkv_bias": False}, SEED, toks)
        assert float(jnp.max(jnp.abs(other - ref))) > 1e-2


def test_gaps_of_the_references_own_choice_are_zero_and_the_control_fails(case):
    model, toks, ref = case
    prompt, n = toks[0, :24].tolist(), 12
    # greedy continuation by the reference itself, teacher-forced
    seq = list(prompt)
    for _ in range(n):
        lg = family.forward_logits(model, SEED, np.asarray([seq + [0] * (40 - len(seq))], np.int32))
        seq.append(int(jnp.argmax(lg[0, len(seq) - 1])))
    served = seq[len(prompt):]
    res = family.gaps(model, SEED, [(prompt, served)], 64, control=True)
    assert len(res["gap"]) == n and max(res["gap"]) == 0.0 and all(res["agree"])
    assert max(res["control_gap"]) > 0.05  # int4 weights put another token first somewhere
    wrong = list(served)
    wrong[5] = (wrong[5] + 1) % model["vocab_size"] or 1
    bad = family.gaps(model, SEED, [(prompt, wrong)], 64)
    assert bad["gap"][5] > 0.5 and not bad["agree"][5]

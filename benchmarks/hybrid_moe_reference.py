"""The hybrid_moe family's plain reference (families/hybrid_moe.py).

The layer as the model's description gives it, float32, every matmul at
"highest": pre-norm blocks; GQA whose q and k pass an RMSNorm over each head's
values; a layer whose window is set rotates q and k (RoPE) and lets query i
see keys j <= i with i - j < window, a layer without one rotates NOTHING and
sees every j <= i; a leading dense SwiGLU layer, then sigmoid-routed experts
with a correction bias on the choice (top-k of `s + b`, weights `s` without
`b`, normalised over all k chosen, scaled) and a shared expert. No kernel, no
cache, no batching: one sequence at a time, a layer at a time, an expert at a
time. Nothing of the program is imported; weights come from
`hybrid_moe_weights.py` by the run's seed.

**The share.** Where the configuration holds a share of a layer's experts
(`num_experts` of `num_experts_routed`, from `first_expert_held`), the router
scores and chooses over ALL of them and the held experts' part of the result
is summed: what the absent experts would add is left out here as in the
program, and that partial result goes on to the next layer. The vocabulary's
slice is a smaller vocabulary.

Departures from the published code, immaterial under seeded weights: RoPE in
split halves (`reference._rope`); norm placement, q/k norm and no rotation on
full layers are the configuration file's `assumed`.

The comparison (`gaps`) judges the MEAN gap of `WINDOW` consecutive served
tokens, as the latent_moe family does and for its reason: through a stack of
seeded top-k routers no bfloat16 program follows a float32 reference token by
token (`latent_moe_reference.gaps`; its body is repeated here over this
family's hidden states until a `benchmark` PR lets `reference.gaps` take them
as an argument, PERF.md section 7). The pieces that judge one token and apply
the head are `reference.py`'s own, on the same keys (a row is embedded under
this family's scale of the table, `_embed_rows`); the router, the
SwiGLU and the window's mean are `latent_moe_reference.py`'s.
"""

from __future__ import annotations

import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np

import hybrid_moe_weights as W
import reference as R
# the router (sigmoid scores, a correction bias on the choice, normalised and scaled weights: the same
# published keys), the SwiGLU and the mean over WINDOW served tokens are the latent_moe reference's own
from latent_moe_reference import WINDOW, _swiglu, route, window_means



def _real(q, model: dict, int4: bool):
    return R._real(q, W.fan_in_of(q.shape), model, int4)


def routed_part(model: dict, leaves: dict, h, *, int4: bool = False):
    """The held experts' part of a routed layer's result over h [s, d]: every
    held expert over the whole sequence, kept where the router chose it."""
    m = W.dims(model)
    chosen, w = route(model, leaves, h)

    def expert(y, e):
        share = jnp.sum(jnp.where(chosen == m["first"] + e, w, 0.0), axis=-1, keepdims=True)  # [s, 1]
        out = _swiglu(h, *(_real(leaves[n][e], model, int4) for n in ("w_gate", "w_up", "w_down")))
        return y + share * out, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(m["Eh"]))
    return y


def shared_part(model: dict, leaves: dict, h, *, int4: bool = False):
    return _swiglu(h, *(_real(leaves[n], model, int4) for n in ("ws_gate", "ws_up", "ws_down")))


def layer_forward(model: dict, leaves: dict, x, *, window: int, moe: bool, int4: bool = False, routed: bool = True):
    """One layer over one sequence x [s, d] (float32); `window` 0 = a full layer;
    `routed` False leaves the held experts' part out (the second control of `gaps`)."""
    m = W.dims(model)
    eps, theta = model["rms_norm_eps"], float(model["rope_theta"])
    s = x.shape[0]
    pos = jnp.arange(s)

    def real(name):
        return _real(leaves[name], model, int4)

    h = R._rms(x, leaves["attn_norm"], eps)
    q = (h @ real("wq")).reshape(s, m["hq"], m["hd"])
    kv = (h @ real("wkv")).reshape(s, m["hkv"], 2, m["hd"])
    k, v = kv[:, :, 0], kv[:, :, 1]
    q, k = R._rms(q, leaves["q_norm"], eps), R._rms(k, leaves["k_norm"], eps)
    if window > 0:  # a full layer carries no positional encoding
        q, k = R._rope(q, pos, theta), R._rope(k, pos, theta)
    x = x + R._attention(q, k, v, window) @ real("wo")

    h = R._rms(x, leaves["mlp_norm"], eps)
    if not moe:
        return x + _swiglu(h, real("w_gate"), real("w_up"), real("w_down"))
    if routed:
        x = x + routed_part(model, leaves, h, int4=int4)
    return x + shared_part(model, leaves, h, int4=int4)


def _embed_rows(model_t, key, tokens, int4):
    """`reference._embed_rows` under this family's scale of the table (`W.embed_scale`: fan-in 1)."""
    model = dict(model_t)
    return R._real(W.table(model, key, 2), 1, model, int4)[tokens]


def _frozen(model: dict) -> tuple:
    """`reference._frozen` (scalars only) with the one nested number a layer
    reads, the published `rope_parameters.rope_theta`, beside them."""
    return R._frozen({**model, "rope_theta": model["rope_parameters"]["rope_theta"]})


@functools.partial(jax.jit, static_argnames=("model_t", "window", "moe", "int4", "routed"))
def _one_layer(model_t, lkey, xs, window, moe, int4, routed):
    model = dict(model_t)
    leaves = W.layer_leaves(model, lkey, moe)
    return jax.lax.map(
        lambda x: layer_forward(model, leaves, x, window=window, moe=moe, int4=int4, routed=routed), xs)


def hidden_states(model: dict, seed: int, tokens, *, int4: bool = False, routed: bool = True):
    """Final hidden states (before the last norm) of each row of `tokens`
    [n, s], layer by layer."""
    key, mt = W.base_key(seed), _frozen(model)
    with jax.default_matmul_precision("highest"):
        xs = _embed_rows(mt, key, jnp.asarray(tokens, jnp.int32), int4)
        for i, (lkey, window) in enumerate(zip(W.layer_keys(key, model), W.windows(model))):
            xs = _one_layer(mt, lkey, xs, window, i >= model["first_k_dense_replace"], int4, routed)
    return xs


def forward_logits(model: dict, seed: int, tokens, *, int4: bool = False):
    """Full-sequence logits [n, s, vocab]: for tests at small sizes."""
    xs = hidden_states(model, seed, tokens, int4=int4)
    n, s, d = xs.shape
    with jax.default_matmul_precision("highest"):
        return R._logits(_frozen(model), W.base_key(seed), xs.reshape(n * s, d), int4).reshape(n, s, -1)


def gaps(model: dict, seed: int, samples: list, seq_len: int, *, control: bool = False) -> dict:
    """`reference.gaps` over this family's hidden states (the same padding to
    `seq_len`, the same blocks of rows, the same judge of one token), then,
    request by request, the mean of every `WINDOW` consecutive served tokens'
    gaps: `gap` and `control_gap` hold one such mean a served token, so the
    widest is the worst stretch any sampled request had; `token_gap` and
    `token_control_gap` keep each token's own. The routing is the reference's
    own throughout; nothing of the program's is followed.

    With `control` a SECOND control is read beside the int4 one and printed
    with it, `no_routed_gap` / `token_no_routed_gap`: the first choice of the
    reference with the held experts' part left out of every routed layer,
    judged by the same judge as if it had been served. It says whether the
    limit would catch a program that dropped its share of the experts; the
    run's verdict stays the int4 control's (`run.py` judges `control_gap`)."""
    n = len(samples)
    longest = max(len(p) + len(sv) - 1 for p, sv in samples)
    if longest > seq_len:
        raise ValueError(f"a sampled request holds {longest} tokens, over {seq_len}")
    toks = np.zeros((n, seq_len), np.int32)
    where = []  # (sample, first row, count)
    for i, (prompt, served) in enumerate(samples):
        seq = list(prompt) + list(served[:-1])
        toks[i, : len(seq)] = seq
        where.append((i, len(prompt) - 1, len(served)))
    hid = hidden_states(model, seed, toks)
    hid_c = hidden_states(model, seed, toks, int4=True) if control else None
    hid_r = hidden_states(model, seed, toks, routed=False) if control else None
    key, mt = W.base_key(seed), _frozen(model)
    res = {"gap": [], "agree": [], "control_gap": [], "token_gap": [], "token_control_gap": [], "per_request": [],
           "no_routed_gap": [], "token_no_routed_gap": []}
    with jax.default_matmul_precision("highest"):
        for i, first_row, count in where:
            served = np.asarray(samples[i][1], np.int32)
            req = {"gap": [], "agree": [], "control_gap": [], "no_routed_gap": []}
            for a in range(0, count, R.ROW_BLOCK):
                b = min(count, a + R.ROW_BLOCK)
                pad = R.ROW_BLOCK - (b - a)
                rows = jnp.pad(hid[i, first_row + a : first_row + b], ((0, pad), (0, 0)))
                rows_c = rows if hid_c is None else jnp.pad(
                    hid_c[i, first_row + a : first_row + b], ((0, pad), (0, 0)))
                sv = jnp.pad(jnp.asarray(served[a:b]), (0, pad))
                out = jax.device_get(R._judge(mt, key, rows, rows_c, sv, control))
                if control:  # the second control's first choice, judged as if it had been served
                    rows_r = jnp.pad(hid_r[i, first_row + a : first_row + b], ((0, pad), (0, 0)))
                    first = R._logits(mt, key, rows_r, False).argmax(axis=-1)
                    out["no_routed_gap"] = jax.device_get(R._judge(mt, key, rows, rows, first, False))["gap"]
                for k, v in out.items():
                    req[k] += np.asarray(v)[: b - a].tolist()
            res["agree"] += req["agree"]
            res["token_gap"] += req["gap"]
            res["token_control_gap"] += req["control_gap"]
            res["gap"] += window_means(req["gap"]).tolist()
            if control:
                res["control_gap"] += window_means(req["control_gap"]).tolist()
                res["token_no_routed_gap"] += req["no_routed_gap"]
                res["no_routed_gap"] += window_means(req["no_routed_gap"]).tolist()
            res["per_request"].append(float(window_means(req["gap"]).max()))
    print(f"[hybrid_moe] a gap is the mean of {WINDOW} consecutive served tokens': widest {max(res['gap']):.4f}, over "
          f"every served token {np.mean(res['token_gap']):.4f}; a single token's widest {max(res['token_gap']):.3f}"
          + (f"; the int4 control's widest mean {max(res['control_gap']):.4f}, over every token "
             f"{np.mean(res['token_control_gap']):.4f}, a single token's widest {max(res['token_control_gap']):.3f}"
             f"; the reference WITHOUT its routed part: widest mean {max(res['no_routed_gap']):.4f}, over every token "
             f"{np.mean(res['token_no_routed_gap']):.4f}" if control else ""), file=sys.stderr, flush=True)
    return res

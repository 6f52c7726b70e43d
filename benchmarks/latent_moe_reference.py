"""The latent_moe family's plain reference (families/latent_moe.py).

The published equations in their EXPANDED form, float32, every matmul at
"highest": multi-head latent attention with the keys and values of every head
written out from the latent (no absorbed query, no cache), a leading dense
SwiGLU layer, then sigmoid-routed experts with a correction bias on the choice
(top-k of `s + b`, weights `s` without `b`, normalised, scaled), every expert
applied to the tokens routed to it and the shared expert to all: no token is
ever dropped, and the routing is the reference's own, never the program's.
Nothing of the program is imported; weights come from `latent_moe_weights.py`
by the run's seed, a layer at a time, an expert at a time.

Departure from the published code, immaterial under seeded weights: RoPE in
split halves (`reference._rope`), which is the interleaved form up to a fixed
permutation of the rope columns of `wq_b` and `wkv_a`.

The comparison (`gaps`) and the int4 control are `reference.py`'s, over this
family's hidden states: the embedding, the final norm and the head are the
dense family's own functions on the same keys. What is judged is the MEAN gap
of `WINDOW` consecutive served tokens, not a single token's (`gaps` says why).
"""

from __future__ import annotations

import functools
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

import latent_moe_weights as W
import reference as R

F32 = jnp.float32

# Served tokens a gap is averaged over before it is judged (`gaps`); a request that served fewer is
# one window.
WINDOW = 256


def _real(q, model: dict, int4: bool):
    return R._real(q, W.fan_in_of(q.shape), model, int4)


def _attention(q, k, v):
    """Causal softmax attention, one block of queries at a time. q, k
    [s, h, dqk], v [s, h, dv] -> [s, h * dv]."""
    s, h, dqk = q.shape
    dv = v.shape[-1]
    q = q / math.sqrt(dqk)
    kpos = jnp.arange(s)
    nblk = -(-s // R.Q_BLOCK)
    qb = jnp.pad(q, ((0, nblk * R.Q_BLOCK - s), (0, 0), (0, 0))).reshape(nblk, R.Q_BLOCK, h, dqk)

    def block(args):
        qi, i = args
        qpos = i * R.Q_BLOCK + jnp.arange(R.Q_BLOCK)
        logits = jnp.einsum("qhd,shd->hqs", qi, k)
        logits = jnp.where((kpos[None, :] <= qpos[:, None])[None], logits, -1e30)
        return jnp.einsum("hqs,shd->qhd", jax.nn.softmax(logits, axis=-1), v)

    out = jax.lax.map(block, (qb, jnp.arange(nblk)))
    return out.reshape(nblk * R.Q_BLOCK, h * dv)[:s]


def route(model: dict, leaves: dict, h):
    """-> (chosen experts [s, k], their weights [s, k]), float32."""
    scores = jax.nn.sigmoid(h @ leaves["w_router"].astype(F32))
    _, chosen = jax.lax.top_k(scores + leaves["router_bias"].astype(F32), model["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if model.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen, w * float(model["routed_scaling_factor"])


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def layer_forward(model: dict, leaves: dict, x, *, moe: bool, int4: bool = False):
    """One layer over one sequence x [s, d] (float32)."""
    m = W.dims(model)
    eps, theta = model["rms_norm_eps"], float(model["rope_theta"])
    s = x.shape[0]
    pos = jnp.arange(s)

    def real(name):
        return _real(leaves[name], model, int4)

    h = R._rms(x, leaves["attn_norm"], eps)
    q = (R._rms(h @ real("wq_a"), leaves["q_norm"], eps) @ real("wq_b")).reshape(s, m["hq"], m["dn"] + m["dr"])
    kv = h @ real("wkv_a")
    c_kv = R._rms(kv[:, : m["C"]], leaves["kv_norm"], eps)
    k_rope = R._rope(kv[:, None, m["C"]:], pos, theta)  # ONE head, shared by all
    up = (c_kv @ real("wkv_b")).reshape(s, m["hq"], m["dn"] + m["dv"])
    k = jnp.concatenate([up[..., : m["dn"]], jnp.broadcast_to(k_rope, (s, m["hq"], m["dr"]))], axis=-1)
    q = jnp.concatenate([q[..., : m["dn"]], R._rope(q[..., m["dn"]:], pos, theta)], axis=-1)
    x = x + _attention(q, k, up[..., m["dn"]:]) @ real("wo")

    h = R._rms(x, leaves["mlp_norm"], eps)
    if not moe:
        return x + _swiglu(h, real("w_gate"), real("w_up"), real("w_down"))
    chosen, w = route(model, leaves, h)

    def expert(y, e):
        """Expert e over the whole sequence, kept where it was chosen."""
        share = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1, keepdims=True)  # [s, 1]
        out = _swiglu(h, *(_real(leaves[n][e], model, int4) for n in ("w_gate", "w_up", "w_down")))
        return y + share * out, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(m["E"]))
    return x + y + _swiglu(h, real("ws_gate"), real("ws_up"), real("ws_down"))


@functools.partial(jax.jit, static_argnames=("model_t", "moe", "int4"))
def _one_layer(model_t, lkey, xs, moe, int4):
    model = dict(model_t)
    leaves = W.layer_leaves(model, lkey, moe)
    return jax.lax.map(lambda x: layer_forward(model, leaves, x, moe=moe, int4=int4), xs)


def hidden_states(model: dict, seed: int, tokens, *, int4: bool = False):
    """Final hidden states (before the last norm) of each row of `tokens`
    [n, s], layer by layer."""
    key, mt = W.base_key(seed), R._frozen(model)
    with jax.default_matmul_precision("highest"):
        xs = R._embed_rows(mt, key, jnp.asarray(tokens, jnp.int32), int4)
        for i, lkey in enumerate(W.layer_keys(key, model)):
            xs = _one_layer(mt, lkey, xs, i >= model["first_k_dense_replace"], int4)
    return xs


def forward_logits(model: dict, seed: int, tokens, *, int4: bool = False):
    """Full-sequence logits [n, s, vocab]: for tests at small sizes."""
    xs = hidden_states(model, seed, tokens, int4=int4)
    n, s, d = xs.shape
    with jax.default_matmul_precision("highest"):
        return R._logits(R._frozen(model), W.base_key(seed), xs.reshape(n * s, d), int4).reshape(n, s, -1)


def window_means(values, window: int = WINDOW) -> np.ndarray:
    """At every position the mean of the `window` values that end there (the
    first window's mean before one is whole; all of them where there are fewer)."""
    v = np.asarray(values, np.float64)
    w = min(window, len(v))
    total = np.concatenate([[0.0], np.cumsum(v)])
    means = (total[w:] - total[:-w]) / w
    return np.concatenate([np.full(w - 1, means[0]), means])


def gaps(model: dict, seed: int, samples: list, seq_len: int, *, control: bool = False) -> dict:
    """`reference.gaps` over this family's hidden states (the same padding to
    `seq_len`, the same blocks of rows, the same judge of one token), then,
    request by request, the mean of every `WINDOW` consecutive served tokens'
    gaps: `gap` and `control_gap` hold one such mean a served token, so the
    widest is the worst stretch any sampled request had; `token_gap` and
    `token_control_gap` keep each token's own.

    Why a mean. Through a stack of top-k routers with experts drawn alone, no
    program with bfloat16 activations follows a float32 reference token by
    token: rounding (1% of a hidden state after the first layer, 2-4% mid-stack)
    tips near ties of the router, the two experts of a tie differ by a whole
    expert's output, later routers tip in turn, and at 13 layers nearly every
    token's hidden state is off by more than 5%. A sound program's WIDEST
    single gap then reads what the int4 control's does (4-5 against ~5 on the
    chip), while its served tokens still lie far nearer the reference's first
    choice on average (PERF.md section 6, PR 29, has the readings). The routing
    is the reference's own throughout; nothing of the program's is followed."""
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
    key, mt = W.base_key(seed), R._frozen(model)
    res = {"gap": [], "agree": [], "control_gap": [], "token_gap": [], "token_control_gap": [], "per_request": []}
    with jax.default_matmul_precision("highest"):
        for i, first_row, count in where:
            served = np.asarray(samples[i][1], np.int32)
            req = {"gap": [], "agree": [], "control_gap": []}
            for a in range(0, count, R.ROW_BLOCK):
                b = min(count, a + R.ROW_BLOCK)
                pad = R.ROW_BLOCK - (b - a)
                rows = jnp.pad(hid[i, first_row + a : first_row + b], ((0, pad), (0, 0)))
                rows_c = rows if hid_c is None else jnp.pad(
                    hid_c[i, first_row + a : first_row + b], ((0, pad), (0, 0)))
                sv = jnp.pad(jnp.asarray(served[a:b]), (0, pad))
                out = jax.device_get(R._judge(mt, key, rows, rows_c, sv, control))
                for k, v in out.items():
                    req[k] += np.asarray(v)[: b - a].tolist()
            res["agree"] += req["agree"]
            res["token_gap"] += req["gap"]
            res["token_control_gap"] += req["control_gap"]
            res["gap"] += window_means(req["gap"]).tolist()
            if control:
                res["control_gap"] += window_means(req["control_gap"]).tolist()
            res["per_request"].append(float(window_means(req["gap"]).max()))
    print(f"[latent_moe] a gap is the mean of {WINDOW} consecutive served tokens': widest {max(res['gap']):.4f}, over "
          f"every served token {np.mean(res['token_gap']):.4f}; a single token's widest {max(res['token_gap']):.3f}"
          + (f"; the int4 control's widest mean {max(res['control_gap']):.4f}, over every token "
             f"{np.mean(res['token_control_gap']):.4f}, a single token's widest {max(res['token_control_gap']):.3f}"
             if control else ""), file=sys.stderr, flush=True)
    return res

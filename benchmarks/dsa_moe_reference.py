"""The dsa_moe family's plain reference (families/dsa_moe.py).

The published equations of a DeepSeek-V3.2 layer in their EXPANDED form,
float32: every matmul of two activations at "highest", every matmul of a
weight with exact products and float32 sums (`_dot`). Multi-head latent
attention with every head's keys and values written out from the latent (no
absorbed query, no cache), YaRN rope (the inverse frequencies blended
between the correction dims of beta_fast and beta_slow, the score scale
times yarn_mscale squared);
the lightning indexer's FULL score matrix I[t, s] = sum_j w[t, j]
relu(q_idx[t, j] . k_idx[s]) over the causal rows and an exact top-k per
query (`index_topk`, or every row at a shorter context), outside of which a
query's attention weight is zero; a leading dense SwiGLU layer, then
sigmoid-routed experts with a correction bias on the choice, group-limited
(the experts of the `topk_group` best of `n_group` groups by their two best
`s + b` are eligible), weights `s` without `b`, normalised and scaled, and a
shared expert. Queries are taken in blocks (`Q_BLOCK`), so that a 13k-token
sequence's [heads, queries, rows] scores fit on the chip. No kernel, no
cache, no batching: one sequence at a time, a layer at a time, an expert at
a time. Sampled requests that open with one document pass that stretch once
(`hidden_states`' `shared`): a causal layer's rows do not depend on later
rows, so the stretch gives each request the same keys, values and index
keys, and only its own rows attend them. Nothing of the program is
imported; weights come from `dsa_moe_weights.py` by the run's seed.

**The share.** Where the configuration holds a share of a layer's experts
(`n_routed_experts` of `n_routed_experts_routed`, from `first_expert_held`),
the router scores and chooses over ALL of them, in all groups, and the held
experts' part of the result is summed: what the absent experts would add is
left out here as in the program. The vocabulary's slice is a smaller
vocabulary.

Departures from the published code, immaterial under seeded weights: RoPE in
split halves (`reference._rope`'s convention; the indexer's rope columns are
its first `qk_rope_head_dim`), and the indexer in float32 with no Hadamard
rotation (orthonormal on both q and k, so q . k is unchanged in exact
arithmetic) where the published one runs FP8.

The comparison (`gaps`) judges the MEAN gap of `WINDOW` consecutive served
tokens, as the latent_moe and hybrid_moe families do and for their reason
(`latent_moe_reference.gaps`): through a stack of top-k routers, and now a
top-2,048 selection besides, no bfloat16 program follows a float32
reference token by token. The pieces that judge one token and apply the head
are `reference.py`'s own, on the same keys.
"""

from __future__ import annotations

import functools
import math
import sys
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

import dsa_moe_weights as W
import reference as R
from latent_moe_reference import WINDOW, window_means

F32 = jnp.float32
Q_BLOCK = 64  # query rows per attention block: [128 heads, 64, 14k rows] f32 is 470 MB


def _dot(x, q, model: dict, int4: bool):
    """x [..., in] float32 times the real weight `q * s` [in, out]
    (`reference._real`'s, int4's 15 levels with `int4`), in float32. A
    weight's levels are integers of at most 8 bits, exact in bfloat16, so x
    goes in as the three bfloat16 pieces its 24 bits split into exactly:
    every product is exact and the sums are float32's. That is the accuracy
    of "highest" in half its passes, and a few seconds of the chip's
    compiler where "highest" took it tens of seconds a matmul."""
    s = W.scale_of(W.fan_in_of(q.shape), W.dtype_of(model)).astype(F32)
    levels = q.astype(F32)
    if int4:
        step = 127.0 / 7.0
        levels, s = jnp.clip(jnp.round(levels / step), -7, 7), s * step
    levels = levels.astype(jnp.bfloat16)
    out = 0.0
    for _ in range(3):
        piece = x.astype(jnp.bfloat16)
        out = out + jnp.matmul(piece, levels, precision=jax.lax.Precision.DEFAULT, preferred_element_type=F32)
        x = x - piece.astype(F32)
    return out * s


def _swiglu(h, gate, up, down, model: dict, int4: bool):
    return _dot(jax.nn.silu(_dot(h, gate, model, int4)) * _dot(h, up, model, int4), down, model, int4)


def yarn(model: dict) -> tuple[jnp.ndarray, float, float]:
    """(inverse frequencies [rope / 2], the factor of cos and sin, the latent
    score's factor) of the configuration's rope: YaRN's where `rope_scaling`
    says so, else plain."""
    dr, theta = model["qk_rope_head_dim"], float(model["rope_theta"])
    inv = 1.0 / (theta ** (np.arange(0, dr, 2, dtype=np.float64) / dr))
    rs = model.get("rope_scaling")
    if not rs:
        return jnp.asarray(inv, F32), 1.0, 1.0
    factor, orig = float(rs["factor"]), int(rs["original_max_position_embeddings"])

    def corr(rot):
        return dr * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(corr(float(rs["beta_fast"]))), 0)
    high = min(math.ceil(corr(float(rs["beta_slow"]))), dr - 1)
    ramp = np.clip((np.arange(dr // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    keep = 1.0 - ramp
    inv = inv / factor * (1.0 - keep) + inv * keep

    def mscale(m):
        return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0

    m_all = float(rs.get("mscale_all_dim") or 0)
    trig = mscale(float(rs.get("mscale", 1))) / mscale(m_all)
    return jnp.asarray(inv, F32), trig, (mscale(m_all) ** 2 if m_all else 1.0)


def _rope(x, pos, inv, trig=1.0):
    """x [s, h, d]; pairs (x[..., :d/2], x[..., d/2:]), cos and sin times `trig`."""
    ang = pos.astype(F32)[:, None, None] * inv
    sin, cos = trig * jnp.sin(ang), trig * jnp.cos(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _layer_norm(x, weight, bias, eps=1e-6):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * weight.astype(F32) + bias.astype(F32)


def sparse_attention(q, k, v, qi, ki, wi, *, scale: float, topk: int, start: int = 0):
    """Causal attention where query t sees only its top-`topk` rows by index
    score, one block of queries at a time. The queries are rows `start` on:
    q [s - start, h, dqk], qi [s - start, ih, di], wi [s - start, ih]; the
    keys every row: k [s, h, dqk], v [s, h, dv], ki [s, di] -> [s - start, h * dv]."""
    s, h, dqk = k.shape
    n = q.shape[0]
    dv = v.shape[-1]
    kpos = jnp.arange(s)
    nblk = -(-n // Q_BLOCK)
    pad = ((0, nblk * Q_BLOCK - n),)
    qb = jnp.pad(q, pad + ((0, 0), (0, 0))).reshape(nblk, Q_BLOCK, h, dqk)
    qib = jnp.pad(qi, pad + ((0, 0), (0, 0))).reshape(nblk, Q_BLOCK, qi.shape[1], qi.shape[2])
    wib = jnp.pad(wi, pad + ((0, 0),)).reshape(nblk, Q_BLOCK, wi.shape[1])
    kk = min(int(topk), s)

    def block(args):
        qq, qqi, wwi, i = args
        qpos = start + i * Q_BLOCK + jnp.arange(Q_BLOCK)
        causal = kpos[None, :] <= qpos[:, None]
        index = jnp.einsum("qjs,qj->qs", jax.nn.relu(jnp.einsum("qjd,sd->qjs", qqi, ki)), wwi)
        _, chosen = jax.lax.top_k(jnp.where(causal, index, -jnp.inf), kk)
        picked = jnp.zeros((Q_BLOCK, s), bool).at[jnp.arange(Q_BLOCK)[:, None], chosen].set(True) & causal
        logits = jnp.einsum("qhd,shd->hqs", qq * scale, k)
        logits = jnp.where(picked[None], logits, -1e30)
        return jnp.einsum("hqs,shd->qhd", jax.nn.softmax(logits, axis=-1), v)

    out = jax.lax.map(block, (qb, qib, wib, jnp.arange(nblk)))
    return out.reshape(nblk * Q_BLOCK, h * dv)[:n]


def route(model: dict, leaves: dict, h):
    """-> (chosen experts [s, k] among all the router's, their weights [s, k]), float32."""
    m = W.dims(model)
    scores = jax.nn.sigmoid(h @ leaves["w_router"].astype(F32))
    choose = scores + leaves["router_bias"].astype(F32)
    _, chosen = jax.lax.top_k(W.group_limited(choose, m["groups"], m["topk_groups"]), m["k"])
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if model.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen, w * float(model["routed_scaling_factor"])


def routed_part(model: dict, leaves: dict, h, *, int4: bool = False):
    """The held experts' part of a routed layer's result over h [s, d]."""
    m = W.dims(model)
    chosen, w = route(model, leaves, h)

    def expert(y, e):
        share = jnp.sum(jnp.where(chosen == m["first"] + e, w, 0.0), axis=-1, keepdims=True)
        out = _swiglu(h, *(leaves[n][e] for n in ("w_gate", "w_up", "w_down")), model, int4)
        return y + share * out, None

    return jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(m["Eh"]))[0]


def layer_forward(model: dict, leaves: dict, x, *, moe: bool, int4: bool = False, start: int = 0):
    """One layer over one sequence x [s, d] (float32): the rows `start` on
    [s - start, d], every row before them giving keys, values and index
    keys only (causal: what a row gives does not depend on later rows)."""
    m = W.dims(model)
    eps = model["rms_norm_eps"]
    s = x.shape[0]
    pos = jnp.arange(s)
    inv, trig, m2 = yarn(model)
    dn, dr = m["dn"], m["dr"]

    def mm(x, name):
        return _dot(x, leaves[name], model, int4)

    h = R._rms(x, leaves["attn_norm"], eps)
    cq = R._rms(mm(h[start:], "wq_a"), leaves["q_norm"], eps)
    q = mm(cq, "wq_b").reshape(s - start, m["hq"], dn + dr)
    kv = mm(h, "wkv_a")
    c_kv = R._rms(kv[:, : m["C"]], leaves["kv_norm"], eps)
    k_rope = _rope(kv[:, None, m["C"]:], pos, inv, trig)  # ONE head, shared by all
    up = mm(c_kv, "wkv_b").reshape(s, m["hq"], dn + m["dv"])
    k = jnp.concatenate([up[..., :dn], jnp.broadcast_to(k_rope, (s, m["hq"], dr))], axis=-1)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos[start:], inv, trig)], axis=-1)
    # the lightning indexer
    qi = mm(cq, "wq_idx").reshape(s - start, m["ih"], m["di"])
    qi = jnp.concatenate([_rope(qi[..., :dr], pos[start:], inv, trig), qi[..., dr:]], axis=-1)
    ki = _layer_norm(mm(h, "wk_idx"), leaves["k_idx_norm"], leaves["k_idx_bias"])[:, None]
    ki = jnp.concatenate([_rope(ki[..., :dr], pos, inv, trig), ki[..., dr:]], axis=-1)[:, 0]
    wi = mm(h[start:], "w_idx") / math.sqrt(m["ih"] * m["di"])
    attn = sparse_attention(q, k, up[..., dn:], qi, ki, wi, scale=m2 / math.sqrt(dn + dr), topk=m["topk"],
                            start=start)
    x = x[start:] + mm(attn, "wo")

    h = R._rms(x, leaves["mlp_norm"], eps)
    if not moe:
        return x + _swiglu(h, leaves["w_gate"], leaves["w_up"], leaves["w_down"], model, int4)
    shared_ffn = _swiglu(h, leaves["ws_gate"], leaves["ws_up"], leaves["ws_down"], model, int4)
    return x + routed_part(model, leaves, h, int4=int4) + shared_ffn


def _embed_rows(model_t, key, tokens, int4):
    """`reference._embed_rows` under this family's scale of the table (fan-in 1)."""
    model = dict(model_t)
    return R._real(W.table(model, key, 2), 1, model, int4)[tokens]


def _frozen(model: dict) -> tuple:
    """`reference._frozen` (scalars only) with the nested `rope_scaling`
    beside them as a tuple of its items."""
    rs = model.get("rope_scaling")
    return R._frozen(model) + ((("rope_scaling", tuple(sorted(rs.items()))),) if rs else ())


def _thawed(model_t) -> dict:
    model = dict(model_t)
    if "rope_scaling" in model:
        model["rope_scaling"] = dict(model["rope_scaling"])
    return model


@functools.partial(jax.jit, static_argnames=("model_t", "moe"))
def _leaves(model_t, lkey, moe):
    return W.layer_leaves(_thawed(model_t), lkey, moe)


@functools.partial(jax.jit, static_argnames=("model_t", "moe", "int4", "start"))
def _one_layer(model_t, leaves, xs, moe, int4, start):
    model = _thawed(model_t)
    return jax.lax.map(lambda x: layer_forward(model, leaves, x, moe=moe, int4=int4, start=start), xs)


def compile_ahead(calls: list) -> None:
    """Compile each (jitted function, arguments or their shapes, static
    keywords) side by side, in threads; the calls that follow find them
    built. The chip's compiler takes half a minute over a layer's program
    (its float32 attention at "highest"): one after another they took
    minutes."""
    def one(call):
        fn, args, statics = call
        with jax.default_matmul_precision("highest"):
            fn.trace(*args, **statics).lower().compile()

    with ThreadPoolExecutor(max(1, len(calls))) as pool:
        list(pool.map(one, calls))


def _shape(x):
    return jax.ShapeDtypeStruct(x.shape, x.dtype)


def hidden_states(model: dict, seed: int, tokens, *, int4: bool = False, shared: int = 0, also=()):
    """Final hidden states (before the last norm) of each row of `tokens`
    [n, s], layer by layer, at its positions `shared` on: [n, s - shared, d].
    Rows that open with the same `shared` tokens (a document that requests
    share) take that stretch's pass once: a causal layer's row depends on
    no later row, so the stretch gives each of them the same keys, values
    and index keys, and the rest of each row attends them. `also` are more
    calls to compile beside the layers' (`compile_ahead`)."""
    key, mt = W.base_key(seed), _frozen(model)
    toks = np.asarray(tokens, np.int32)
    n, s = toks.shape
    lkeys = W.layer_keys(key, model)
    kinds = [i >= model["first_k_dense_replace"] for i in range(len(lkeys))]
    heads, which = (np.unique(toks[:, :shared], axis=0, return_inverse=True) if shared else (None, None))
    d = model["hidden_size"]
    calls = list(also)
    for moe in sorted(set(kinds)):
        lk = lkeys[kinds.index(moe)]
        leaves = jax.tree.map(_shape, jax.eval_shape(functools.partial(_leaves, mt, moe=moe), lk))
        calls.append((_leaves, (mt, _shape(lk)), {"moe": moe}))
        calls.append((_one_layer, (mt, leaves, jax.ShapeDtypeStruct((n, s, d), F32)),
                      {"moe": moe, "int4": int4, "start": shared}))
        if shared:
            calls.append((_one_layer, (mt, leaves, jax.ShapeDtypeStruct((len(heads), shared, d), F32)),
                          {"moe": moe, "int4": int4, "start": 0}))
    compile_ahead(calls)
    with jax.default_matmul_precision("highest"):
        xs = _embed_rows(mt, key, jnp.asarray(toks[:, shared:]), int4)
        hs = _embed_rows(mt, key, jnp.asarray(heads), int4) if shared else None
        for lkey, moe in zip(lkeys, kinds):
            leaves = _leaves(mt, lkey, moe=moe)
            if shared:
                full = jnp.concatenate([hs[jnp.asarray(which.reshape(-1))], xs], axis=1)
                xs = _one_layer(mt, leaves, full, moe=moe, int4=int4, start=shared)
                hs = _one_layer(mt, leaves, hs, moe=moe, int4=int4, start=0)
            else:
                xs = _one_layer(mt, leaves, xs, moe=moe, int4=int4, start=0)
            del leaves
    return xs


def shared_head(seqs: list, first: int) -> int:
    """Tokens, in whole blocks of `Q_BLOCK` and up to row `first`, that open
    two or more of `seqs` alike: the document that sampled requests share."""
    best = 0
    for i, a in enumerate(seqs):
        for b in seqs[i + 1:]:
            m = min(len(a), len(b))
            diff = np.flatnonzero(a[:m] != b[:m])
            best = max(best, int(diff[0]) if diff.size else m)
    return min(best, first) // Q_BLOCK * Q_BLOCK


def forward_logits(model: dict, seed: int, tokens, *, int4: bool = False):
    """Full-sequence logits [n, s, vocab]: for tests at small sizes."""
    xs = hidden_states(model, seed, tokens, int4=int4)
    n, s, d = xs.shape
    with jax.default_matmul_precision("highest"):
        return R._logits(R._frozen(model), W.base_key(seed), xs.reshape(n * s, d), int4).reshape(n, s, -1)


def gaps(model: dict, seed: int, samples: list, seq_len: int, *, control: bool = False) -> dict:
    """`latent_moe_reference.gaps` over this family's hidden states: the same
    padding to `seq_len`, the same blocks of rows, the same judge of one token
    (`reference._judge`), then, request by request, the mean of every `WINDOW`
    consecutive served tokens' gaps (`gap`, `control_gap`); `token_gap` and
    `token_control_gap` keep each token's own. The routing and the
    selection are the reference's own throughout."""
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
    shared = shared_head([toks[i, : len(p) + len(sv) - 1] for i, (p, sv) in enumerate(samples)],
                         min(first for _, first, _ in where))
    key, mt = W.base_key(seed), R._frozen(model)
    rows_in = jax.ShapeDtypeStruct((R.ROW_BLOCK, model["hidden_size"]), F32)
    judge = (R._judge, (mt, _shape(key), rows_in, rows_in, jax.ShapeDtypeStruct((R.ROW_BLOCK,), jnp.int32)),
             {"control": control})
    hid = hidden_states(model, seed, toks, shared=shared, also=[judge])
    hid_c = hidden_states(model, seed, toks, int4=True, shared=shared) if control else None
    where = [(i, first - shared, count) for i, first, count in where]
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
    print(f"[dsa_moe] a gap is the mean of {WINDOW} consecutive served tokens': widest {max(res['gap']):.4f}, over "
          f"every served token {np.mean(res['token_gap']):.4f}; a single token's widest {max(res['token_gap']):.3f}"
          + (f"; the int4 control's widest mean {max(res['control_gap']):.4f}, over every token "
             f"{np.mean(res['token_control_gap']):.4f}, a single token's widest {max(res['token_control_gap']):.3f}"
             if control else ""), file=sys.stderr, flush=True)
    return res

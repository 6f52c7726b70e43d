"""The plain reference, and the comparison that decides `correct`.

A straightforward forward pass of the architecture (GQA, RoPE in split
halves, SwiGLU, RMSNorm, optional q/k/v bias, optional sliding window) in
float32 with every matmul at "highest" precision: no kernels, no cache, no
batching tricks. It imports nothing of the program and takes nothing the
program made: weights come from `weights.py` by the run's seed, one layer at
a time, so that a 7B model fits beside nothing else on a freed chip.

What is compared: for a sample of the requests the window finished, the
reference runs once over each prompt with its SERVED tokens (teacher
forcing) and reads, at every served token, how far that token's reference
logit lies below the reference's best (`gap`). A served path that computes
what the configuration states picks the reference's best token or, where
rounding flips a near tie, one a hair below it. The widest gap of the sample
is the number held to the cell's limit.

The control is this same reference with every int8 weight re-rounded to int4
(the precision below the configuration's): at each position it reads the gap
of the token that the lower precision puts first. It does not decode.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

import weights as W

F32 = jnp.float32
Q_BLOCK = 512  # query rows per attention block
ROW_BLOCK = 256  # hidden rows per block of logits


def _real(q, fan_in: int, model: dict, int4: bool):
    """The real-valued weight `q * s` in float32; with `int4`, q is first
    re-rounded to the 15 levels of a symmetric int4."""
    s = W.scale_of(fan_in, W.dtype_of(model)).astype(F32)
    qf = q.astype(F32)
    if int4:
        step = 127.0 / 7.0
        qf = jnp.clip(jnp.round(qf / step), -7, 7) * step
    return qf * s


def _rms(x, leaf, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + leaf.astype(F32))


def _rope(x, positions, theta):
    """x [s, h, hd]; pairs are (x[..., :hd/2], x[..., hd/2:])."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = positions.astype(F32)[:, None, None] * inv
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v, window: int):
    """Causal (and windowed) softmax attention, one block of queries at a
    time. q [s, hq, hd], k/v [s, hkv, hd]; query head i reads kv head
    i // (hq / hkv)."""
    s, hq, hd = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    qg = (q / math.sqrt(hd)).reshape(s, hkv, g, hd)
    kpos = jnp.arange(s)
    nblk = -(-s // Q_BLOCK)
    pad = nblk * Q_BLOCK - s
    qg = jnp.pad(qg, ((0, pad), (0, 0), (0, 0), (0, 0))).reshape(nblk, Q_BLOCK, hkv, g, hd)

    def block(args):
        qb, i = args
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        logits = jnp.einsum("qkgd,skd->kgqs", qb, k)
        mask = kpos[None, :] <= qpos[:, None]
        if window > 0:
            mask &= kpos[None, :] > qpos[:, None] - window
        logits = jnp.where(mask[None, None], logits, -1e30)
        p = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", p, v)

    out = jax.lax.map(block, (qg, jnp.arange(nblk)))
    return out.reshape(nblk * Q_BLOCK, hq * hd)[:s]


def layer_forward(model: dict, leaves: dict, x, *, int4: bool = False):
    """One layer over one sequence x [s, d] (float32)."""
    m = W.dims(model)
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    window = int(model.get("sliding_window") or 0)
    s = x.shape[0]
    pos = jnp.arange(s)

    fan_in = W.fan_ins(model)

    def mm(h, name):
        return h @ _real(leaves[name], fan_in[name], model, int4)

    h = _rms(x, leaves["attn_norm"], eps)
    q = mm(h, "wq")
    kv = mm(h, "wkv")
    if model.get("qkv_bias"):
        q = q + leaves["bq"].astype(F32)
        kv = kv + leaves["bkv"].astype(F32)
    q = q.reshape(s, m["hq"], m["hd"])
    kv = kv.reshape(s, m["hkv"], 2, m["hd"])
    k, v = kv[:, :, 0], kv[:, :, 1]
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    x = x + mm(_attention(q, k, v, window), "wo")
    h = _rms(x, leaves["mlp_norm"], eps)
    return x + mm(jax.nn.silu(mm(h, "w_gate")) * mm(h, "w_up"), "w_down")


def _frozen(model: dict) -> tuple:
    """The model group as a hashable static argument (scalars only)."""
    return tuple(sorted((k, v) for k, v in model.items() if isinstance(v, (int, float, str, bool))))


# The jitted pieces are module-level and take the seed's key as an ARGUMENT,
# so that one compilation serves every seed and the persistent cache hits.


@functools.partial(jax.jit, static_argnames=("model_t", "int4"))
def _embed_rows(model_t, key, tokens, int4):
    model = dict(model_t)
    return _real(W.table(model, key, 2), model["hidden_size"], model, int4)[tokens]


@functools.partial(jax.jit, static_argnames=("model_t", "int4"))
def _one_layer(model_t, lkey, xs, int4):
    model = dict(model_t)
    leaves = W.layer_leaves(model, lkey)
    return jax.lax.map(lambda x: layer_forward(model, leaves, x, int4=int4), xs)


def _head(model_t, key, rows, int4):
    """Final norm, then the untied head: float32 logits [r, vocab]."""
    model = dict(model_t)
    h = _rms(rows, W.final_norm(model, key), model["rms_norm_eps"])
    return h @ _real(W.table(model, key, 3), model["hidden_size"], model, int4).T


_logits = jax.jit(_head, static_argnames=("model_t", "int4"))


@functools.partial(jax.jit, static_argnames=("model_t", "control"))
def _judge(model_t, key, rows, rows_c, served, control):
    """One block of rows: the served token's gap below the reference's best,
    and with `control` the gap of the int4 reference's first choice."""
    lg = _head(model_t, key, rows, False)
    best = lg.max(axis=-1)
    out = {"gap": best - jnp.take_along_axis(lg, served[:, None], axis=-1)[:, 0],
           "agree": lg.argmax(axis=-1) == served}
    if control:
        first = _head(model_t, key, rows_c, True).argmax(axis=-1)
        out["control_gap"] = best - jnp.take_along_axis(lg, first[:, None], axis=-1)[:, 0]
    return out


def hidden_states(model: dict, seed: int, tokens, *, int4: bool = False):
    """Final hidden states (before the last norm) of each row of `tokens`
    [n, s], layer by layer: each layer's weights are drawn, used on every
    sequence in turn, and dropped."""
    key, mt = W.base_key(seed), _frozen(model)
    with jax.default_matmul_precision("highest"):
        xs = _embed_rows(mt, key, jnp.asarray(tokens, jnp.int32), int4)
        for lkey in W.layer_keys(key, model):
            xs = _one_layer(mt, lkey, xs, int4)
    return xs


def forward_logits(model: dict, seed: int, tokens, *, int4: bool = False):
    """Full-sequence logits [n, s, vocab]: for tests at small sizes."""
    xs = hidden_states(model, seed, tokens, int4=int4)
    n, s, d = xs.shape
    with jax.default_matmul_precision("highest"):
        return _logits(_frozen(model), W.base_key(seed), xs.reshape(n * s, d), int4).reshape(n, s, -1)


def gaps(model: dict, seed: int, samples: list, seq_len: int, *, control: bool = False) -> dict:
    """`samples`: (prompt token ids, served token ids) pairs. Returns the
    gap of every served token and, with `control`, of the token the int4
    reference puts first at the same positions.

    Served token j of a request with a prompt of P tokens is predicted from
    position P-1+j of the sequence prompt + served[:-1]. Every sequence is
    padded to `seq_len` (the engine's longest), so that ONE compiled shape
    serves every run: a new shape costs the TPU's compiler ~40 s."""
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

    key, mt = W.base_key(seed), _frozen(model)

    res = {"gap": [], "agree": [], "control_gap": [], "per_request": []}
    with jax.default_matmul_precision("highest"):
        for i, first_row, count in where:
            served = np.asarray(samples[i][1], np.int32)
            req_gap = []
            for a in range(0, count, ROW_BLOCK):
                b = min(count, a + ROW_BLOCK)
                pad = ROW_BLOCK - (b - a)
                rows = jnp.pad(hid[i, first_row + a : first_row + b], ((0, pad), (0, 0)))
                rows_c = None if hid_c is None else jnp.pad(
                    hid_c[i, first_row + a : first_row + b], ((0, pad), (0, 0)))
                sv = jnp.pad(jnp.asarray(served[a:b]), (0, pad))
                out = jax.device_get(_judge(mt, key, rows, rows if rows_c is None else rows_c, sv, control))
                for k, v in out.items():
                    res[k] += np.asarray(v)[: b - a].tolist()
                req_gap += np.asarray(out["gap"])[: b - a].tolist()
            res["per_request"].append(max(req_gap))
    return res

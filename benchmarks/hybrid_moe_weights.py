"""The hybrid_moe family's weights from `--seed` (families/hybrid_moe.py).

As `weights.py` does for the dense family: drawn by the benchmark with
jax.random alone and handed to both sides; the plain reference draws the SAME
arrays from the same keys, one layer at a time. Nothing of the program is
imported here. `model` is the configuration file's `model` group, the
published config.json keys of an `exaone_moe` decoder and, where the chip
holds a share of a layer, what it holds:

- `num_experts` is the count HELD here (in `reduced`), `num_experts_routed`
  the router's width (the published count; absent: every expert is held) and
  `first_expert_held` the first held expert's index among them;
- `vocab_size` is the slice's rows.

Layout:
- every matmul weight is int8 `[in, out]`, uniform in [-127, 127], ONE scale a
  tensor, `1 / (73 * sqrt(fan_in))` in the model's dtype; the routed experts
  are stacks `[held, in, out]` with that one scale for the whole stack. Each
  expert is drawn from a key of ITS OWN, split by its index among ALL the
  router's experts: a share of the experts holds the very arrays the uncut
  layer holds at those indices (the test that the shares add up rests on it);
- attention: `wq [d, heads * head_dim]`, `wkv [d, 2 * kv_heads * head_dim]`
  packed [kv head][k | v][head_dim] as `weights.py` packs it, `wo`; `q_norm`
  and `k_norm` `[head_dim]`;
- the router `w_router [d, routed]` is float32, N(0, 1/d), never quantised,
  and its correction bias `router_bias [routed]` float32 is CALIBRATED to
  these weights as a balanced training leaves it (`even_bias`): both decide
  WHICH experts, in float32 on both sides;
- norm leaves hold the published scale MINUS ONE, N(0, 0.1);
- the first `first_k_dense_replace` layers keep a dense SwiGLU of
  `intermediate_size`; the rest hold the routed experts of
  `moe_intermediate_size` and `num_shared_experts` shared ones fused to one
  FFN of `num_shared_experts * moe_intermediate_size`;
- `embed` / `unembed` / `final_norm` are `weights.py`'s own arrays (same
  keys); the embedding's ONE scale is this family's (`embed_scale`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import weights as W

base_key, dtype_of, scale_of, table, final_norm = (
    W.base_key, W.dtype_of, W.scale_of, W.table, W.final_norm)


def dims(model: dict) -> dict:
    held = model["num_experts"]
    return {
        "d": model["hidden_size"], "ff": model["intermediate_size"],
        "fe": model["moe_intermediate_size"], "Eh": held,
        "E": int(model.get("num_experts_routed") or held),
        "first": int(model.get("first_expert_held") or 0),
        "k": model["num_experts_per_tok"], "ns": model["num_shared_experts"],
        "hq": model["num_attention_heads"], "hkv": model["num_key_value_heads"],
        "hd": model["head_dim"], "L": model["num_hidden_layers"],
        "dense": model["first_k_dense_replace"], "vocab": model["vocab_size"],
    }


def windows(model: dict) -> tuple:
    """The keys each of the model's layers attends, 0 = all (`sliding_windows`
    of the published file, cut to the layers that are run)."""
    return tuple(int(w) for w in model["sliding_windows"][: model["num_hidden_layers"]])


def attention_shapes(model: dict) -> dict:
    m = dims(model)
    return {"wq": (m["d"], m["hq"] * m["hd"]), "wkv": (m["d"], 2 * m["hkv"] * m["hd"]),
            "wo": (m["hq"] * m["hd"], m["d"])}


def mlp_shapes(model: dict, moe: bool) -> dict:
    """int8 tensors of a layer's feed-forward that are ONE draw each: `[in, out]`
    (the routed experts' stacks are drawn an expert at a time, `expert_stacks`)."""
    m = dims(model)
    if not moe:
        return {"w_gate": (m["d"], m["ff"]), "w_up": (m["d"], m["ff"]), "w_down": (m["ff"], m["d"])}
    fs = m["ns"] * m["fe"]
    return {"ws_gate": (m["d"], fs), "ws_up": (m["d"], fs), "ws_down": (fs, m["d"])}


def expert_shapes(model: dict) -> dict:
    m = dims(model)
    return {"w_gate": (m["d"], m["fe"]), "w_up": (m["d"], m["fe"]), "w_down": (m["fe"], m["d"])}


def fan_in_of(shape: tuple) -> int:
    return shape[-2]


def embed_scale(model: dict):
    """The embedding table's one scale: 1 / 73, a row's values of unit variance
    (`scale_of` at fan-in 1: a table is looked up, not contracted). Under a
    matmul weight's 1 / (73 sqrt(d)), which the accepted families' tables
    have, a token enters at a tenth of what layer 0's attention adds to it
    (1 / sqrt(d) an element against the mean of some fifty value rows of unit
    variance), so every token of a sequence leaves the first layer as its
    window's mean, the stack keeps one direction a sequence (a third of the
    last hidden state's energy at a twin of width 768, CPU) and a sequence
    has favourite experts for as long as it lasts: one chip's share of a
    sequence's pairs had a standard deviation of 4.5% (19% a layer; my chip
    run, PR 33, call G), which no bias, one vector a layer, can even, and
    the cell's timing turned on the seed. Where every expert is held that
    costs nothing; where a share is, it is the spread the driver refused the
    cell for (PERF.md section 6, PR 33)."""
    return scale_of(1, dtype_of(model))


def layer_keys(key, model: dict):
    return jax.random.split(jax.random.fold_in(key, 1), model["num_hidden_layers"])


def expert_stacks(model: dict, key) -> dict:
    """The held experts' three stacks `[held, in, out]`: expert e of the
    router's `E` from the e-th of E keys, whichever share holds it."""
    m = dims(model)
    keys = jax.random.split(key, m["E"])[m["first"] : m["first"] + m["Eh"]]

    def one(k):
        ks = jax.random.split(k, 3)
        return {name: jax.random.randint(kk, shape, -127, 128, jnp.int8)
                for kk, (name, shape) in zip(ks, expert_shapes(model).items())}

    # an expert at a time (lax.map, not vmap): the random bits of a stack are
    # four times its int8 bytes
    return jax.lax.map(one, keys)


BIAS_DRAWS, BIAS_STEPS, BIAS_RATE = 1 << 15, 16, 0.05


def even_bias(w_router, norm, key, k: int):
    """The correction bias `[routed]` under which every expert is chosen
    equally often: what the load-balancing update of a sigmoid router's
    training (the bias of an expert chosen too often goes down) leaves, and the
    reason the leaf exists. A bias drawn N(0, 0.01) does the opposite: near
    the top-8-of-128 threshold 0.01 of bias moves an expert's load by ~13%,
    so which experts were favoured, and with it how much of a step's routed
    work lands on ONE chip's share, turned on the seed (PERF.md section 6,
    PR 33). A function of the layer's own weights and key alone, so the
    program, the reference and every share get the same numbers: the logits
    of a normed hidden state with no preferred direction are N(0, W^T g^2 W)
    (`g` the norm's scale); `BIAS_DRAWS` of them are drawn, and `BIAS_STEPS`
    times each expert's bias moves against the log of its share of the
    choices. It evens the load POOLED over sequences; the experts that one
    sequence favours for as long as it lasts are no bias's to even. The
    matmuls say their precision: the caller's default differs between the
    two sides."""
    hi = jax.lax.Precision.HIGHEST
    w = w_router * (1.0 + norm.astype(jnp.float32))[:, None]
    cov = jnp.matmul(w.T, w, precision=hi)
    n = cov.shape[0]
    draws = jax.random.normal(key, (BIAS_DRAWS, n), jnp.float32)
    scores = jax.nn.sigmoid(jnp.matmul(draws, jnp.linalg.cholesky(cov).T, precision=hi))

    def step(b, _):
        chosen = jax.lax.top_k(scores + b, k)[1]
        share = jnp.zeros(n, jnp.float32).at[chosen.reshape(-1)].add(n / (k * BIAS_DRAWS))  # 1 when even
        b = b - BIAS_RATE * jnp.log(jnp.maximum(share, 1e-3))
        return b - jnp.mean(b), None

    return jax.lax.scan(step, jnp.zeros(n, jnp.float32), None, length=BIAS_STEPS)[0]


def layer_leaves(model: dict, key, moe: bool) -> dict:
    """One layer's arrays from its key."""
    m, dt = dims(model), dtype_of(model)
    ks = iter(jax.random.split(key, 16))
    out = {name: jax.random.randint(next(ks), shape, -127, 128, jnp.int8)
           for name, shape in {**attention_shapes(model), **mlp_shapes(model, moe)}.items()}
    for name, width in (("attn_norm", m["d"]), ("q_norm", m["hd"]), ("k_norm", m["hd"]), ("mlp_norm", m["d"])):
        out[name] = (0.1 * jax.random.normal(next(ks), (width,), jnp.float32)).astype(dt)
    if moe:
        out["w_router"] = jax.random.normal(next(ks), (m["d"], m["E"]), jnp.float32) / jnp.sqrt(float(m["d"]))
        out["router_bias"] = even_bias(out["w_router"], out["mlp_norm"], next(ks), m["k"])
        out.update(expert_stacks(model, next(ks)))
    return out


def all_arrays(model: dict, key) -> dict:
    """The whole model as stacked groups, for ONE jitted call on the device:
    {"embed", "unembed", "final_norm", "dense": {leaf: [Ld, ...]}, "moe": {leaf: [Lm, ...]}}."""
    keys, n_dense = layer_keys(key, model), model["first_k_dense_replace"]
    return {
        "embed": table(model, key, 2), "unembed": table(model, key, 3),
        "final_norm": final_norm(model, key),
        # a layer at a time: a whole group's random bits would not fit beside it
        "dense": jax.lax.map(lambda k: layer_leaves(model, k, False), keys[:n_dense]),
        "moe": jax.lax.map(lambda k: layer_leaves(model, k, True), keys[n_dense:]),
    }

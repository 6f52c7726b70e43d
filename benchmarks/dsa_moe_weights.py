"""The dsa_moe family's weights from `--seed` (families/dsa_moe.py).

As `weights.py` does for the dense family: drawn by the benchmark with
jax.random alone and handed to both sides; the plain reference draws the SAME
arrays from the same keys, one layer at a time. Nothing of the program is
imported here. `model` is the configuration file's `model` group, the
published config.json keys of a `deepseek_v32` decoder and, where the chip
holds a share of a layer, what it holds:

- `n_routed_experts` is the count HELD here (in `reduced`),
  `n_routed_experts_routed` the router's width (the published count; absent:
  every expert is held) and `first_expert_held` the first held expert's index
  among them;
- `vocab_size` is the slice's rows.

Layout:
- every matmul weight is int8 `[in, out]`, uniform in [-127, 127], ONE scale a
  tensor, `1 / (73 * sqrt(fan_in))` in the model's dtype; the routed experts
  are stacks `[held, in, out]` with that one scale for the whole stack, each
  expert from a key of ITS OWN among all the router's experts
  (`hybrid_moe_weights.expert_stacks`'s rule), so a share holds the arrays
  the uncut layer holds at those indices;
- latent attention as `latent_moe_weights.py` lays it out: `wq_a`, `wq_b`
  (per head [nope | rope]), `wkv_a` ([latent | rope key]), `wkv_b` (per head
  [k_nope | v]), `wo`;
- the lightning indexer: `wq_idx [q_lora, index_heads * index_head_dim]` (per
  head [rope | rest]), `wk_idx [d, index_head_dim]`, `w_idx [d, index_heads]`
  (int8 like every projection), and its key's LayerNorm `k_idx_norm` (the
  published weight itself, 1 + N(0, 0.1)) and `k_idx_bias` N(0, 0.1), float;
- the router `w_router [d, routed]` is float32, N(0, 1/d), never quantised,
  and its correction bias `router_bias [routed]` float32 is CALIBRATED to
  these weights under group-limited choice (`even_bias`): both decide WHICH
  experts, in float32 on both sides;
- RMSNorm leaves hold the published scale MINUS ONE, N(0, 0.1);
- the first `first_k_dense_replace` layers keep a dense SwiGLU of
  `intermediate_size`; the rest hold the routed experts of
  `moe_intermediate_size` and `n_shared_experts` shared ones fused to one FFN;
- `embed` / `unembed` / `final_norm` are `weights.py`'s own arrays (same
  keys); the embedding's ONE scale is `hybrid_moe_weights.embed_scale`'s (a
  row of unit variance, which a chip that holds a share of the experts
  needs: PERF.md section 6).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import hybrid_moe_weights as HW
import weights as W

base_key, dtype_of, scale_of, table, final_norm = (
    W.base_key, W.dtype_of, W.scale_of, W.table, W.final_norm)


def dims(model: dict) -> dict:
    held = model["n_routed_experts"]
    return {
        "d": model["hidden_size"], "ff": model["intermediate_size"],
        "fe": model["moe_intermediate_size"], "Eh": held,
        "E": int(model.get("n_routed_experts_routed") or held),
        "first": int(model.get("first_expert_held") or 0),
        "k": model["num_experts_per_tok"], "ns": model["n_shared_experts"],
        "groups": int(model.get("n_group") or 1), "topk_groups": int(model.get("topk_group") or 1),
        "hq": model["num_attention_heads"], "ql": model["q_lora_rank"], "C": model["kv_lora_rank"],
        "dn": model["qk_nope_head_dim"], "dr": model["qk_rope_head_dim"], "dv": model["v_head_dim"],
        "ih": model["index_n_heads"], "di": model["index_head_dim"], "topk": model["index_topk"],
        "L": model["num_hidden_layers"], "dense": model["first_k_dense_replace"],
        "vocab": model["vocab_size"],
    }


def attention_shapes(model: dict) -> dict:
    m = dims(model)
    return {
        "wq_a": (m["d"], m["ql"]), "wq_b": (m["ql"], m["hq"] * (m["dn"] + m["dr"])),
        "wkv_a": (m["d"], m["C"] + m["dr"]), "wkv_b": (m["C"], m["hq"] * (m["dn"] + m["dv"])),
        "wo": (m["hq"] * m["dv"], m["d"]),
    }


def indexer_shapes(model: dict) -> dict:
    m = dims(model)
    return {"wq_idx": (m["ql"], m["ih"] * m["di"]), "wk_idx": (m["d"], m["di"]), "w_idx": (m["d"], m["ih"])}


def mlp_shapes(model: dict, moe: bool) -> dict:
    """int8 tensors of a layer's feed-forward that are ONE draw each (the
    routed experts' stacks are drawn an expert at a time, `expert_stacks`)."""
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

    return jax.lax.map(one, keys)


def group_limited(choose, groups: int, keep: int):
    """DeepSeek-V3's noaux_tc eligibility over choice scores [..., E]: the
    experts of all but the `keep` groups whose two best scores sum highest
    become -inf. One group: unchanged."""
    if groups <= 1:
        return choose
    shape = choose.shape
    by_group = choose.reshape(shape[:-1] + (groups, shape[-1] // groups))
    best = jax.lax.top_k(by_group, min(2, shape[-1] // groups))[0].sum(axis=-1)
    kth = jax.lax.top_k(best, keep)[0][..., -1:]
    return jnp.where((best >= kth)[..., None], by_group, -jnp.inf).reshape(shape)


def even_bias(w_router, norm, key, k: int, groups: int, keep: int):
    """`hybrid_moe_weights.even_bias` under group-limited choice: the
    correction bias `[routed]` under which every expert is chosen equally
    often when only the `keep` best of `groups` groups are eligible, as the
    load-balancing update of training leaves it. The same draws, steps and
    rate; the matmuls say their precision."""
    hi = jax.lax.Precision.HIGHEST
    w = w_router * (1.0 + norm.astype(jnp.float32))[:, None]
    cov = jnp.matmul(w.T, w, precision=hi)
    n = cov.shape[0]
    draws = jax.random.normal(key, (HW.BIAS_DRAWS, n), jnp.float32)
    scores = jax.nn.sigmoid(jnp.matmul(draws, jnp.linalg.cholesky(cov).T, precision=hi))

    def step(b, _):
        chosen = jax.lax.top_k(group_limited(scores + b, groups, keep), k)[1]
        share = jnp.zeros(n, jnp.float32).at[chosen.reshape(-1)].add(n / (k * HW.BIAS_DRAWS))
        b = b - HW.BIAS_RATE * jnp.log(jnp.maximum(share, 1e-3))
        return b - jnp.mean(b), None

    return jax.lax.scan(step, jnp.zeros(n, jnp.float32), None, length=HW.BIAS_STEPS)[0]


def layer_leaves(model: dict, key, moe: bool) -> dict:
    """One layer's arrays from its key."""
    m, dt = dims(model), dtype_of(model)
    ks = iter(jax.random.split(key, 24))
    shapes = {**attention_shapes(model), **indexer_shapes(model), **mlp_shapes(model, moe)}
    out = {name: jax.random.randint(next(ks), shape, -127, 128, jnp.int8) for name, shape in shapes.items()}
    for name, width in (("attn_norm", m["d"]), ("q_norm", m["ql"]), ("kv_norm", m["C"]), ("mlp_norm", m["d"])):
        out[name] = (0.1 * jax.random.normal(next(ks), (width,), jnp.float32)).astype(dt)
    out["k_idx_norm"] = (1.0 + 0.1 * jax.random.normal(next(ks), (m["di"],), jnp.float32)).astype(dt)
    out["k_idx_bias"] = (0.1 * jax.random.normal(next(ks), (m["di"],), jnp.float32)).astype(dt)
    if moe:
        out["w_router"] = jax.random.normal(next(ks), (m["d"], m["E"]), jnp.float32) / jnp.sqrt(float(m["d"]))
        out["router_bias"] = even_bias(out["w_router"], out["mlp_norm"], next(ks), m["k"], m["groups"],
                                       m["topk_groups"])
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

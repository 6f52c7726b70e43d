"""The latent_moe family's weights from `--seed` (families/latent_moe.py).

As `weights.py` does for the dense family: drawn by the benchmark with
jax.random alone and handed to both sides; the plain reference draws the SAME
arrays from the same keys, one layer at a time. Nothing of the program is
imported here. `model` is the configuration file's `model` group, the
published config.json keys of a `glm4_moe_lite` / DeepSeek-V2-style decoder.

Layout:
- every matmul weight is int8 `[in, out]`, uniform in [-127, 127], ONE scale a
  tensor, `1 / (73 * sqrt(fan_in))` in the model's dtype; the routed experts
  are stacks `[E, in, out]` with that one scale for the whole stack;
- attention: `wq_a [d, q_lora]`, `wq_b [q_lora, heads * (nope + rope)]` (per
  head [nope | rope]), `wkv_a [d, kv_lora + rope]` ([latent | rope key]),
  `wkv_b [kv_lora, heads * (nope + v)]` (per head [k_nope | v]), `wo
  [heads * v, d]`; RoPE in split halves over the rope dims;
- the router `w_router [d, E]` is float32, N(0, 1/d), never quantised, and its
  correction bias `router_bias [E]` is float32 N(0, 0.01): both decide WHICH
  experts, in float32 on both sides;
- norm leaves hold the published scale MINUS ONE, N(0, 0.1): `attn_norm`,
  `q_norm` (q_lora), `kv_norm` (kv_lora), `mlp_norm`;
- the first `first_k_dense_replace` layers keep a dense SwiGLU of
  `intermediate_size`; the rest hold `n_routed_experts` experts of
  `moe_intermediate_size` and `n_shared_experts` shared ones fused to one FFN
  of `n_shared_experts * moe_intermediate_size`;
- `embed` / `unembed` / `final_norm` are `weights.py`'s own (same keys).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import weights as W

base_key, dtype_of, scale_of, table, final_norm = (
    W.base_key, W.dtype_of, W.scale_of, W.table, W.final_norm)


def dims(model: dict) -> dict:
    return {
        "d": model["hidden_size"], "ff": model["intermediate_size"],
        "fe": model["moe_intermediate_size"], "E": model["n_routed_experts"],
        "k": model["num_experts_per_tok"], "ns": model["n_shared_experts"],
        "hq": model["num_attention_heads"], "ql": model["q_lora_rank"], "C": model["kv_lora_rank"],
        "dn": model["qk_nope_head_dim"], "dr": model["qk_rope_head_dim"], "dv": model["v_head_dim"],
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


def mlp_shapes(model: dict, moe: bool) -> dict:
    """int8 tensors of a layer's feed-forward: `[in, out]`, experts `[E, in, out]`."""
    m = dims(model)
    if not moe:
        return {"w_gate": (m["d"], m["ff"]), "w_up": (m["d"], m["ff"]), "w_down": (m["ff"], m["d"])}
    fs = m["ns"] * m["fe"]
    return {
        "w_gate": (m["E"], m["d"], m["fe"]), "w_up": (m["E"], m["d"], m["fe"]),
        "w_down": (m["E"], m["fe"], m["d"]),
        "ws_gate": (m["d"], fs), "ws_up": (m["d"], fs), "ws_down": (fs, m["d"]),
    }


def matmul_shapes(model: dict, moe: bool) -> dict:
    return {**attention_shapes(model), **mlp_shapes(model, moe)}


def fan_in_of(shape: tuple) -> int:
    return shape[-2]


def layer_keys(key, model: dict):
    return jax.random.split(jax.random.fold_in(key, 1), model["num_hidden_layers"])


def layer_leaves(model: dict, key, moe: bool) -> dict:
    """One layer's arrays from its key."""
    m, dt = dims(model), dtype_of(model)
    ks = iter(jax.random.split(key, 20))
    out = {name: jax.random.randint(next(ks), shape, -127, 128, jnp.int8)
           for name, shape in matmul_shapes(model, moe).items()}
    for name, width in (("attn_norm", m["d"]), ("q_norm", m["ql"]), ("kv_norm", m["C"]), ("mlp_norm", m["d"])):
        out[name] = (0.1 * jax.random.normal(next(ks), (width,), jnp.float32)).astype(dt)
    if moe:
        out["w_router"] = jax.random.normal(next(ks), (m["d"], m["E"]), jnp.float32) / jnp.sqrt(float(m["d"]))
        out["router_bias"] = 0.01 * jax.random.normal(next(ks), (m["E"],), jnp.float32)
    return out


def all_arrays(model: dict, key) -> dict:
    """The whole model as stacked groups, for ONE jitted call on the device:
    {"embed", "unembed", "final_norm", "dense": {leaf: [Ld, ...]}, "moe": {leaf: [Lm, ...]}}."""
    keys, n_dense = layer_keys(key, model), model["first_k_dense_replace"]
    return {
        "embed": table(model, key, 2), "unembed": table(model, key, 3),
        "final_norm": final_norm(model, key),
        # a layer at a time (lax.map, not vmap): the random bits of 64 experts are
        # a few times their int8 bytes, and a whole group's would not fit beside it
        "dense": jax.lax.map(lambda k: layer_leaves(model, k, False), keys[:n_dense]),
        "moe": jax.lax.map(lambda k: layer_leaves(model, k, True), keys[n_dense:]),
    }

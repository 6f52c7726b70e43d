"""Weights from `--seed`, made by the benchmark and handed to both sides.

The program is given the int8 tree; the plain reference draws the SAME
arrays from the same keys, one layer at a time, and never touches what the
program holds. Pure jax.random; nothing of the program is imported here.

Layout (the served layout of `gofr_tpu.models`, stated here so the reference
needs no look at the program):
- every matmul weight is int8 `[in, out]`, uniform in [-127, 127], with ONE
  scale per tensor, `1 / (73 * sqrt(fan_in))` rounded to the model's dtype
  (uniform int8 has a standard deviation of ~73, so the real-valued weight
  `q * s` has the usual 1/sqrt(fan_in));
- `wkv` packs its output columns as [kv head][k | v][head_dim];
- norm leaves hold the published scale MINUS ONE (the model multiplies by
  `1 + leaf`), drawn N(0, 0.1); q/k/v biases are drawn N(0, 0.1);
- `embed` and `unembed` are int8 `[vocab, d]` with the scale on d.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

def base_key(seed: int):
    """Any whole number is a seed: two 32-bit words from a SeedSequence
    (`jax.random.PRNGKey` refuses one past 2**31 without x64)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32), impl="threefry2x32")


def dtype_of(model: dict):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[model.get("dtype", "bfloat16")]


def dims(model: dict) -> dict:
    d, ff = model["hidden_size"], model["intermediate_size"]
    hq, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    hd = int(model.get("head_dim") or d // hq)
    return {"d": d, "ff": ff, "hq": hq, "hkv": hkv, "hd": hd,
            "L": model["num_hidden_layers"], "vocab": model["vocab_size"]}


def matmul_shapes(model: dict) -> dict:
    m = dims(model)
    return {
        "wq": (m["d"], m["hq"] * m["hd"]), "wkv": (m["d"], 2 * m["hkv"] * m["hd"]),
        "wo": (m["hq"] * m["hd"], m["d"]), "w_gate": (m["d"], m["ff"]),
        "w_up": (m["d"], m["ff"]), "w_down": (m["ff"], m["d"]),
    }


def fan_ins(model: dict) -> dict:
    """The contraction size of every int8 tensor (both tables: d)."""
    out = {name: shape[0] for name, shape in matmul_shapes(model).items()}
    out["embed"] = out["unembed"] = model["hidden_size"]
    return out


def scale_of(fan_in: int, dtype) -> jnp.ndarray:
    return jnp.asarray(1.0 / (73.0 * math.sqrt(fan_in)), dtype)


def layer_keys(key, model: dict):
    return jax.random.split(jax.random.fold_in(key, 1), model["num_hidden_layers"])


def layer_leaves(model: dict, key) -> dict:
    """One layer's arrays from its key: int8 matmul weights, norm leaves and
    (with `qkv_bias`) the two biases, in the model's dtype."""
    m, dt = dims(model), dtype_of(model)
    ks = iter(jax.random.split(key, 10))
    out = {name: jax.random.randint(next(ks), shape, -127, 128, jnp.int8)
           for name, shape in matmul_shapes(model).items()}
    out["attn_norm"] = (0.1 * jax.random.normal(next(ks), (m["d"],), jnp.float32)).astype(dt)
    out["mlp_norm"] = (0.1 * jax.random.normal(next(ks), (m["d"],), jnp.float32)).astype(dt)
    if model.get("qkv_bias"):
        out["bq"] = (0.1 * jax.random.normal(next(ks), (m["hq"] * m["hd"],), jnp.float32)).astype(dt)
        out["bkv"] = (0.1 * jax.random.normal(next(ks), (2 * m["hkv"] * m["hd"],), jnp.float32)).astype(dt)
    return out


def table(model: dict, key, which: int) -> jnp.ndarray:
    """`embed` (which=2) or `unembed` (which=3): int8 [vocab, d]."""
    m = dims(model)
    return jax.random.randint(jax.random.fold_in(key, which), (m["vocab"], m["d"]), -127, 128, jnp.int8)


def final_norm(model: dict, key) -> jnp.ndarray:
    m = dims(model)
    return (0.1 * jax.random.normal(jax.random.fold_in(key, 4), (m["d"],), jnp.float32)).astype(dtype_of(model))


def all_arrays(model: dict, key) -> dict:
    """The whole model as stacked arrays, for ONE jitted call on the device:
    {"embed", "unembed", "final_norm", "layers": {leaf: [L, ...]}}."""
    return {
        "embed": table(model, key, 2),
        "unembed": table(model, key, 3),
        "final_norm": final_norm(model, key),
        "layers": jax.vmap(lambda k: layer_leaves(model, k))(layer_keys(key, model)),
    }

"""The dense family: GQA decoders with RoPE in split halves, SwiGLU, RMSNorm,
optional q/k/v bias and one sliding window for the whole stack.

A family is the ONE place that knows a configuration's architecture.
`run.py` finds it by the `family` key of the configuration's file and asks it
for these six names and nothing else:

- `program_config(model)`, `program_params(model, seed)`: the program's
  config object, and the seed's weights in the program's tree. The only two
  functions of a family that import anything of `gofr_tpu`.
- `gaps(model, seed, samples, seq_len, *, control=False)` and
  `forward_logits(model, seed, tokens, *, int4=False)`: the plain reference
  and its int4 control (`reference.py`).
- `least_step_seconds(model, pk, *, prefill_contexts, decode_contexts,
  prefill_int8)` and `decode_kv_read_bytes(model, contexts)`: the yardsticks
  of every share of a peak (`costs.py`).

`model` is the configuration file's `model` group; which of its keys exist
is the family's business (here: `weights.dims`, and `sliding_window`,
`qkv_bias`, `rope_theta`, `rms_norm_eps`, `hidden_act`, `dtype`).
"""

from __future__ import annotations

import costs
import reference
import weights as W

gaps = reference.gaps
forward_logits = reference.forward_logits
least_step_seconds = costs.least_step_seconds
decode_kv_read_bytes = costs.decode_kv_read_bytes


def program_config(model: dict):
    import jax.numpy as jnp

    from gofr_tpu.models import TransformerConfig

    hd = int(model.get("head_dim") or model["hidden_size"] // model["num_attention_heads"])
    return TransformerConfig(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_layers=model["num_hidden_layers"], n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], head_dim=hd,
        d_ff=model["intermediate_size"], rope_theta=float(model["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]), act=model.get("hidden_act", "silu"),
        scale_embed=False, sliding_window=int(model.get("sliding_window") or 0),
        qkv_bias=bool(model.get("qkv_bias")),
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[model.get("dtype", "bfloat16")],
    )


def program_params(model: dict, seed: int):
    """The seed's weights in the program's tree: ONE jitted call on the
    device, int8 as served."""
    import jax

    from gofr_tpu.models.quant import QTensor

    dt = W.dtype_of(model)
    fan_in = W.fan_ins(model)

    def qtensor(q, name):
        """The program's scale leaf is [..., 1, out] (tables: [1, d])."""
        return QTensor(q=q, s=jax.numpy.full(q.shape[:-2] + (1, q.shape[-1]), W.scale_of(fan_in[name], dt), dt))

    def build(key):
        a = W.all_arrays(model, key)
        layers = {name: qtensor(x, name) if name in fan_in else x for name, x in a["layers"].items()}
        return {"embed": qtensor(a["embed"], "embed"), "unembed": qtensor(a["unembed"], "unembed"),
                "final_norm": a["final_norm"], "layers": layers}

    return jax.jit(build)(W.base_key(seed))

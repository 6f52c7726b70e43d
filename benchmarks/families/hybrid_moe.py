"""The hybrid_moe family: GQA decoders whose stack MIXES layers that attend a
sliding window (with RoPE) and layers that attend everything (with no
positional encoding at all), an RMSNorm on each head's q and k, a leading
dense SwiGLU layer and then sigmoid-routed experts with a correction bias,
normalised and scaled top-k weights and a shared expert, of which the chip
may hold a SHARE (`exaone_moe`; K-EXAONE-236B-A23B).

The six names of `families/dense.py`'s interface, over
`hybrid_moe_weights.py` (the seed's weights: int8 per tensor, every expert
from a key of its own so that a share holds the uncut layer's arrays, router
and bias float32), `hybrid_moe_reference.py` (the plain reference, given the
same share, with its int4 control) and `hybrid_moe_costs.py` (a token's own
work; 4,096 B a cache row a layer, capped at the window on a windowed layer),
and one name more for the cell's metric of the routed experts:
`moe_least_seconds`.

Of `model` it reads the published keys `hidden_size`, `intermediate_size`,
`moe_intermediate_size`, `num_experts` (the count HELD here),
`num_experts_per_tok`, `num_shared_experts`, `norm_topk_prob`,
`routed_scaling_factor`, `scoring_func`, `first_k_dense_replace`,
`num_hidden_layers`, `num_attention_heads`, `num_key_value_heads`,
`head_dim`, `sliding_windows` (cut to the layers run), `sliding_window`,
`rope_parameters`, `rms_norm_eps`, `vocab_size` (the slice's rows), `dtype`,
and what the cut adds: `num_experts_routed` (the router's width, the
published count) and `first_expert_held`. `n_group` / `topk_group` have to
be 1, `scoring_func` sigmoid and the rope `default`: anything else is
refused, not ignored.
"""

from __future__ import annotations

import hybrid_moe_costs as C
import hybrid_moe_reference as REF
import hybrid_moe_weights as W

gaps = REF.gaps
forward_logits = REF.forward_logits
least_step_seconds = C.least_step_seconds
decode_kv_read_bytes = C.decode_kv_read_bytes
moe_least_seconds = C.moe_least_seconds


def _check(model: dict) -> None:
    if int(model.get("n_group", 1)) != 1 or int(model.get("topk_group", 1)) != 1:
        raise ValueError("the hybrid_moe family routes over ONE group (n_group = topk_group = 1)")
    if model.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError(f"unknown scoring_func {model['scoring_func']!r}: the family knows sigmoid")
    if model["rope_parameters"].get("rope_type", "default") != "default":
        raise ValueError("the hybrid_moe family has no rope scaling: `rope_type` has to be default")
    if {w for w in W.windows(model) if w} - {int(model["sliding_window"])}:
        raise ValueError("`sliding_windows` names another window than `sliding_window`")


def program_config(model: dict):
    import dataclasses

    import jax.numpy as jnp

    from gofr_tpu.models import TransformerConfig

    _check(model)
    needs = {"layer_windows", "qk_norm", "rope_on_window_only", "moe_first_expert", "moe_held_experts"}
    missing = sorted(needs - {f.name for f in dataclasses.fields(TransformerConfig)})
    if missing:  # an older program (the parent of the PR that brought the family): say so, at once
        raise SystemExit(f"benchmarks/families/hybrid_moe.py: this program's TransformerConfig has no {missing}: "
                         "it cannot run a stack that mixes window and full layers, nor hold a share of its experts")
    m = W.dims(model)
    return TransformerConfig(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_layers=model["num_hidden_layers"], n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        d_ff=model["intermediate_size"], rope_theta=float(model["rope_parameters"]["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]), act=model.get("hidden_act", "silu"),
        scale_embed=False,
        layer_windows=W.windows(model), qk_norm=True, rope_on_window_only=True,
        n_experts=m["E"], moe_top_k=model["num_experts_per_tok"],
        moe_score="sigmoid", moe_norm_topk=bool(model.get("norm_topk_prob", True)),
        moe_scale=float(model["routed_scaling_factor"]),
        n_shared_experts=model["num_shared_experts"], moe_d_ff=model["moe_intermediate_size"],
        n_dense_layers=model["first_k_dense_replace"],
        moe_first_expert=m["first"], moe_held_experts=(m["Eh"] if m["Eh"] < m["E"] else 0),
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[model.get("dtype", "bfloat16")],
    )


def program_params(model: dict, seed: int):
    """The seed's weights in the program's tree (two stacked layer groups):
    ONE jitted call on the device, int8 as served."""
    import jax
    import jax.numpy as jnp

    from gofr_tpu.models.quant import QTensor

    _check(model)
    dt = W.dtype_of(model)

    def qtensor(q, s=None):
        """The program's scale leaf is [..., 1, out] (tables: [1, d])."""
        if s is None:
            s = W.scale_of(W.fan_in_of(q.shape) if q.ndim > 2 else q.shape[-1], dt)
        return QTensor(q=q, s=jnp.full(q.shape[:-2] + (1, q.shape[-1]), s, dt))

    def group(leaves):
        return {name: qtensor(x) if x.dtype == jnp.int8 else x for name, x in leaves.items()}

    def build(key):
        a = W.all_arrays(model, key)
        return {"embed": qtensor(a["embed"], W.embed_scale(model)), "unembed": qtensor(a["unembed"]),
                "final_norm": a["final_norm"], "layers": (group(a["dense"]), group(a["moe"]))}

    return jax.jit(build)(W.base_key(seed))

"""The latent_moe family: decoders with multi-head latent attention (a query
bottleneck, ONE normalized latent row of keys and values and one shared rope
key a token a layer), a leading dense SwiGLU layer and then sigmoid-routed
experts with a correction bias, normalised and scaled top-k weights and a
shared expert (`glm4_moe_lite`, DeepSeek-V2/V3-shaped; GLM-4.7-Flash).

The six names of `families/dense.py`'s interface, over
`latent_moe_weights.py` (the seed's weights: int8 per tensor, expert stacks
`[E, in, out]`, router and bias float32), `latent_moe_reference.py` (the plain
reference in the EXPANDED form with its int4 control) and
`latent_moe_costs.py` (a token's own work; 1,152 B a cache row), and one name
more for the cell's metric of the routed experts: `moe_least_seconds`.

Of `model` it reads the published keys `hidden_size`, `intermediate_size`,
`moe_intermediate_size`, `n_routed_experts`, `num_experts_per_tok`,
`n_shared_experts`, `norm_topk_prob`, `routed_scaling_factor`,
`first_k_dense_replace`, `num_hidden_layers`, `num_attention_heads`,
`q_lora_rank`, `kv_lora_rank`, `qk_nope_head_dim`, `qk_rope_head_dim`,
`v_head_dim`, `rope_theta`, `rms_norm_eps`, `vocab_size`, and `dtype`.
`n_group` / `topk_group` have to be 1 (group-limited choice is then the
identity) and `rope_scaling` null: anything else is refused, not ignored.
"""

from __future__ import annotations

import latent_moe_costs as C
import latent_moe_reference as REF
import latent_moe_weights as W

gaps = REF.gaps
forward_logits = REF.forward_logits
least_step_seconds = C.least_step_seconds
decode_kv_read_bytes = C.decode_kv_read_bytes
moe_least_seconds = C.moe_least_seconds


def _check(model: dict) -> None:
    if int(model.get("n_group", 1)) != 1 or int(model.get("topk_group", 1)) != 1:
        raise ValueError("the latent_moe family routes over ONE group (n_group = topk_group = 1)")
    if model.get("rope_scaling") is not None:
        raise ValueError("the latent_moe family has no rope scaling: `rope_scaling` has to be null")
    if model.get("topk_method", "noaux_tc") != "noaux_tc":
        raise ValueError(f"unknown topk_method {model['topk_method']!r}: the family knows noaux_tc")


def program_config(model: dict):
    import dataclasses

    import jax.numpy as jnp

    from gofr_tpu.models import TransformerConfig

    _check(model)
    needs = {"kv_lora_rank", "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
             "moe_score", "moe_norm_topk", "moe_scale", "n_shared_experts", "moe_d_ff", "n_dense_layers"}
    missing = sorted(needs - {f.name for f in dataclasses.fields(TransformerConfig)})
    if missing:  # an older program (the parent of the PR that brought the family): say so, at once
        raise SystemExit(f"benchmarks/families/latent_moe.py: this program's TransformerConfig has no {missing}: "
                         "it cannot run a configuration with latent attention and routed experts")
    return TransformerConfig(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_layers=model["num_hidden_layers"], n_heads=model["num_attention_heads"],
        n_kv_heads=1, head_dim=model["qk_nope_head_dim"] + model["qk_rope_head_dim"],
        d_ff=model["intermediate_size"], rope_theta=float(model["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]), act=model.get("hidden_act", "silu"),
        scale_embed=False,
        n_experts=model["n_routed_experts"], moe_top_k=model["num_experts_per_tok"],
        moe_score="sigmoid", moe_norm_topk=bool(model.get("norm_topk_prob", True)),
        moe_scale=float(model["routed_scaling_factor"]),
        n_shared_experts=model["n_shared_experts"], moe_d_ff=model["moe_intermediate_size"],
        n_dense_layers=model["first_k_dense_replace"],
        kv_lora_rank=model["kv_lora_rank"], q_lora_rank=model["q_lora_rank"],
        qk_nope_head_dim=model["qk_nope_head_dim"], qk_rope_head_dim=model["qk_rope_head_dim"],
        v_head_dim=model["v_head_dim"],
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

    def qtensor(q):
        """The program's scale leaf is [..., 1, out] (tables: [1, d])."""
        s = W.scale_of(W.fan_in_of(q.shape) if q.ndim > 2 else q.shape[-1], dt)
        return QTensor(q=q, s=jnp.full(q.shape[:-2] + (1, q.shape[-1]), s, dt))

    def group(leaves):
        return {name: qtensor(x) if x.dtype == jnp.int8 else x for name, x in leaves.items()}

    def build(key):
        a = W.all_arrays(model, key)
        return {"embed": qtensor(a["embed"]), "unembed": qtensor(a["unembed"]),
                "final_norm": a["final_norm"], "layers": (group(a["dense"]), group(a["moe"]))}

    return jax.jit(build)(W.base_key(seed))

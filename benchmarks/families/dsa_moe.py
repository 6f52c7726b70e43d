"""The dsa_moe family: decoders whose latent (MLA) attention is SPARSE, each
query attending only the rows a lightning indexer scores highest
(DeepSeek-V3.2's DSA), with YaRN rope, a leading dense SwiGLU layer and then
sigmoid-routed experts with group-limited choice, a correction bias,
normalised and scaled top-k weights and a shared expert, of which the chip
may hold a SHARE (`deepseek_v32`; DeepSeek-V3.2).

The six names of `families/dense.py`'s interface, over `dsa_moe_weights.py`
(the seed's weights: int8 per tensor, every expert from a key of its own,
router and bias float32, the bias evened under group-limited choice),
`dsa_moe_reference.py` (the plain reference, expanded, with the indexer's
full score matrix and an exact top-k per query, given the same share, with
its int4 control) and `dsa_moe_costs.py` (a token's own work; 1,536 B a
cache row a layer), and three names more for the cell's metrics:
`moe_least_seconds`, `index_read_bytes` and `sparse_read_bytes`.

Of `model` it reads the published keys `hidden_size`, `intermediate_size`,
`moe_intermediate_size`, `n_routed_experts` (the count HELD here),
`num_experts_per_tok`, `n_shared_experts`, `n_group`, `topk_group`,
`norm_topk_prob`, `routed_scaling_factor`, `scoring_func`, `topk_method`,
`first_k_dense_replace`, `num_hidden_layers`, `num_attention_heads`,
`q_lora_rank`, `kv_lora_rank`, `qk_nope_head_dim`, `qk_rope_head_dim`,
`v_head_dim`, `index_n_heads`, `index_head_dim`, `index_topk`,
`rope_theta`, `rope_scaling` (yarn), `rms_norm_eps`, `vocab_size` (the
slice's rows), `dtype`, and what the cut adds: `n_routed_experts_routed`
(the router's width, the published count) and `first_expert_held`.
`scoring_func` has to be sigmoid, `topk_method` noaux_tc and a rope scaling
yarn or none: anything else is refused, not ignored.
"""

from __future__ import annotations

import dsa_moe_costs as C
import dsa_moe_reference as REF
import dsa_moe_weights as W

gaps = REF.gaps
forward_logits = REF.forward_logits
least_step_seconds = C.least_step_seconds
decode_kv_read_bytes = C.decode_kv_read_bytes
moe_least_seconds = C.moe_least_seconds
index_read_bytes = C.index_read_bytes
sparse_read_bytes = C.sparse_read_bytes


def _check(model: dict) -> None:
    if model.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError(f"unknown scoring_func {model['scoring_func']!r}: the family knows sigmoid")
    if model.get("topk_method", "noaux_tc") != "noaux_tc":
        raise ValueError(f"unknown topk_method {model['topk_method']!r}: the family knows noaux_tc")
    rs = model.get("rope_scaling")
    if rs is not None and rs.get("type", rs.get("rope_type")) != "yarn":
        raise ValueError("the dsa_moe family knows YaRN rope scaling or none: `rope_scaling` says otherwise")


def rope_scaling(model: dict) -> tuple:
    """The program's `rope_scaling`: YaRN's six numbers in its order, or ()."""
    rs = model.get("rope_scaling")
    if not rs:
        return ()
    return (float(rs["factor"]), int(rs["original_max_position_embeddings"]), float(rs["beta_fast"]),
            float(rs["beta_slow"]), float(rs.get("mscale", 1)), float(rs.get("mscale_all_dim", 0)))


def program_config(model: dict):
    import dataclasses

    import jax.numpy as jnp

    from gofr_tpu.models import TransformerConfig

    _check(model)
    needs = {"index_heads", "index_head_dim", "index_topk", "moe_groups", "moe_topk_groups", "rope_scaling",
             "moe_first_expert", "moe_held_experts"}
    missing = sorted(needs - {f.name for f in dataclasses.fields(TransformerConfig)})
    if missing:  # an older program (the parent of the PR that brought the family): say so, at once
        raise SystemExit(f"benchmarks/families/dsa_moe.py: this program's TransformerConfig has no {missing}: "
                         "it cannot run latent attention with a lightning indexer, group-limited routing "
                         "or YaRN rope")
    m = W.dims(model)
    return TransformerConfig(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_layers=model["num_hidden_layers"], n_heads=model["num_attention_heads"],
        n_kv_heads=1, head_dim=model["qk_nope_head_dim"] + model["qk_rope_head_dim"],
        d_ff=model["intermediate_size"], rope_theta=float(model["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]), act=model.get("hidden_act", "silu"),
        scale_embed=False,
        n_experts=m["E"], moe_top_k=model["num_experts_per_tok"],
        moe_score="sigmoid", moe_norm_topk=bool(model.get("norm_topk_prob", True)),
        moe_scale=float(model["routed_scaling_factor"]),
        n_shared_experts=model["n_shared_experts"], moe_d_ff=model["moe_intermediate_size"],
        n_dense_layers=model["first_k_dense_replace"],
        moe_first_expert=m["first"], moe_held_experts=(m["Eh"] if m["Eh"] < m["E"] else 0),
        moe_groups=m["groups"], moe_topk_groups=m["topk_groups"],
        kv_lora_rank=model["kv_lora_rank"], q_lora_rank=model["q_lora_rank"],
        qk_nope_head_dim=model["qk_nope_head_dim"], qk_rope_head_dim=model["qk_rope_head_dim"],
        v_head_dim=model["v_head_dim"], rope_scaling=rope_scaling(model),
        index_heads=model["index_n_heads"], index_head_dim=model["index_head_dim"],
        index_topk=model["index_topk"],
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

"""The latent_moe family's operations and bytes, from the configuration's sizes
(families/latent_moe.py): the yardstick of every share of a peak in its cells.
A token's OWN work is counted, whatever the program computes beside it."""

from __future__ import annotations

import latent_moe_weights as W


def attention_matmul_params(model: dict) -> int:
    """Weights one token multiplies in one layer's attention: q down and up,
    kv down, the per-head up-projection (absorbed or not, the same count), o.
    GLM-4.7-Flash: 2048x768 + 768x5120 + 2048x576 + 512x8960 + 5120x2048 = 21.76 M."""
    return sum(a * b for a, b in W.attention_shapes(model).values())


def expert_params(model: dict) -> int:
    """One expert: gate, up, down (GLM-4.7-Flash: 3 x 2048 x 1536 = 9.437 M)."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def layer_matmul_params(model: dict, moe: bool) -> int:
    """Weights one token multiplies in one layer: attention, then the dense FFN,
    or its top-k routed experts, the shared ones and the router."""
    attn = attention_matmul_params(model)
    if not moe:
        return attn + 3 * model["hidden_size"] * model["intermediate_size"]
    experts = model["num_experts_per_tok"] + model["n_shared_experts"]
    return attn + experts * expert_params(model) + model["hidden_size"] * model["n_routed_experts"]


def layers(model: dict) -> tuple[int, int]:
    n_dense = model["first_k_dense_replace"]
    return n_dense, model["num_hidden_layers"] - n_dense


def body_matmul_flops_per_token(model: dict) -> int:
    n_dense, n_moe = layers(model)
    return 2 * (n_dense * layer_matmul_params(model, False) + n_moe * layer_matmul_params(model, True))


def head_flops_per_logit_row(model: dict) -> int:
    return 2 * model["hidden_size"] * model["vocab_size"]


def row_values(model: dict) -> int:
    """A cache row: the latent and the shared rope key (GLM-4.7-Flash: 576)."""
    return model["kv_lora_rank"] + model["qk_rope_head_dim"]


def attention_flops(model: dict, context: int) -> int:
    """One query token against `context` rows, all layers, in the absorbed form
    the cache forces: heads x (row values for the score + latent values for the
    weighted sum) x 2 FLOP a key (GLM-4.7-Flash: 20 x (576 + 512) x 2 = 43,520)."""
    per_key = 2 * model["num_attention_heads"] * (row_values(model) + model["kv_lora_rank"])
    return per_key * context * model["num_hidden_layers"]


def kv_bytes_per_token(model: dict, kv_dtype_bytes: int = 2) -> int:
    """One token's rows, all layers, whatever the layout pads (GLM-4.7-Flash
    bf16: 1,152 B a layer)."""
    return model["num_hidden_layers"] * row_values(model) * kv_dtype_bytes


def decode_kv_read_bytes(model: dict, contexts, kv_dtype_bytes: int = 2) -> int:
    return sum(contexts) * kv_bytes_per_token(model, kv_dtype_bytes)


def weight_bytes(model: dict, weight_dtype_bytes: int = 1) -> int:
    """Every resident matmul weight and both tables at the served width."""
    n_dense, n_moe = layers(model)
    d, E = model["hidden_size"], model["n_routed_experts"]
    moe_layer = (attention_matmul_params(model)
                 + (E + model["n_shared_experts"]) * expert_params(model))
    body = n_dense * layer_matmul_params(model, False) + n_moe * moe_layer
    return (body + 2 * model["vocab_size"] * d) * weight_dtype_bytes + n_moe * d * E * 4


def least_step_seconds(model: dict, pk: dict, *, prefill_contexts, decode_contexts,
                       prefill_int8: bool = True) -> dict:
    """As costs.least_step_seconds: prefill matmuls at the int8 peak where the
    configuration serves int8, everything else at the bf16 peak."""
    body = body_matmul_flops_per_token(model)
    n_p, n_d = len(prefill_contexts), len(decode_contexts)
    prefill_mm = body * n_p
    decode_mm = body * n_d + head_flops_per_logit_row(model) * n_d
    attn = sum(attention_flops(model, c) for c in prefill_contexts) + sum(
        attention_flops(model, c) for c in decode_contexts)
    t_prefill = prefill_mm / (pk["int8_ops"] if prefill_int8 else pk["bf16_flops"])
    t_rest = (decode_mm + attn) / pk["bf16_flops"]
    return {"prefill_matmul_flops": prefill_mm, "decode_matmul_flops": decode_mm,
            "attention_flops": attn, "seconds": t_prefill + t_rest}


def moe_least_seconds(model: dict, pk: dict, *, pairs: int, touched: int,
                      weight_dtype_bytes: int = 1) -> float:
    """The routed experts' least time for `pairs` (token, expert) rows over
    `touched` experts (summed over layer calls): the larger of streaming each
    touched expert's weights once and of the pairs' FLOPs at the bf16 peak."""
    stream = touched * expert_params(model) * weight_dtype_bytes / pk["hbm_bytes_per_s"]
    flops = pairs * 2 * expert_params(model) / pk["bf16_flops"]
    return max(stream, flops)

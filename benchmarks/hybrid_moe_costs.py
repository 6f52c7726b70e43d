"""The hybrid_moe family's operations and bytes, from the configuration's sizes
(families/hybrid_moe.py): the yardstick of every share of a peak in its cells.
A token's OWN work is counted, whatever the program computes beside it: on a
window layer its attention over at most `window` keys, of its top-k routed
experts the pairs that are computed HERE (the chip's share of the experts,
k x held / routed of them on average), the shared expert, the router, the
sliced head. Worked out by hand for K-EXAONE-236B-A23B's cut beside each."""

from __future__ import annotations

import hybrid_moe_weights as W


def attention_matmul_params(model: dict) -> int:
    """Weights one token multiplies in one layer's attention: q, k and v, o
    (6144 x 8192 + 6144 x 2048 + 8192 x 6144 = 113.2 M)."""
    return sum(a * b for a, b in W.attention_shapes(model).values())


def expert_params(model: dict) -> int:
    """One expert: gate, up, down (3 x 6144 x 2048 = 37.75 M)."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def pairs_here_per_token(model: dict) -> float:
    """Of a token's top-k pairs, those whose expert is held here, on average
    (8 x 16 / 128 = 1)."""
    m = W.dims(model)
    return m["k"] * m["Eh"] / m["E"]


def layer_matmul_params(model: dict, moe: bool) -> float:
    """Weights one token multiplies in one layer: attention, then the dense
    FFN (113.2 M + 3 x 6144 x 18432 = 453.0 M), or its pairs computed here,
    the shared experts and the router (113.2 + 37.75 + 37.75 + 0.79 = 189.5 M)."""
    attn = attention_matmul_params(model)
    if not moe:
        return attn + 3 * model["hidden_size"] * model["intermediate_size"]
    m = W.dims(model)
    return attn + (pairs_here_per_token(model) + m["ns"]) * expert_params(model) + m["d"] * m["E"]


def layers(model: dict) -> tuple[int, int]:
    n_dense = model["first_k_dense_replace"]
    return n_dense, model["num_hidden_layers"] - n_dense


def body_matmul_flops_per_token(model: dict) -> float:
    n_dense, n_moe = layers(model)
    return 2 * (n_dense * layer_matmul_params(model, False) + n_moe * layer_matmul_params(model, True))


def head_flops_per_logit_row(model: dict) -> int:
    return 2 * model["hidden_size"] * model["vocab_size"]


def keys_read(model: dict, context: int) -> int:
    """Keys one query at `context` reads, summed over the layers: all of them
    on a full layer, at most the window on a windowed one (13 layers: 3 x
    context + 10 x min(context, 128))."""
    return sum(min(context, w) if w else context for w in W.windows(model))


def attention_flops(model: dict, context: int) -> int:
    """One query token, all layers: QK^T and PV, 2 FLOP each a key a head a
    head_dim value (4 x 64 x 128 = 32,768 a key)."""
    return 4 * model["num_attention_heads"] * model["head_dim"] * keys_read(model, context)


def kv_row_bytes(model: dict, kv_dtype_bytes: int = 2) -> int:
    """One token's keys and values in one layer (2 x 8 x 128 x 2 = 4,096 B)."""
    return 2 * model["num_key_value_heads"] * model["head_dim"] * kv_dtype_bytes


def decode_kv_read_bytes(model: dict, contexts, kv_dtype_bytes: int = 2) -> int:
    """What the decode tokens at `contexts` had to read of the cache: 4,096 B
    x (3 x context + 10 x min(context, 128)) each."""
    return kv_row_bytes(model, kv_dtype_bytes) * sum(keys_read(model, c) for c in contexts)


def weight_bytes(model: dict, weight_dtype_bytes: int = 1) -> int:
    """Every resident matmul weight and both tables at the served width: the
    dense layer 453 MB, an MoE layer's share 113.2 + 17 x 37.75 = 755 MB and
    its float32 router 3.1 MB, the tables 2 x 118 MB: 9.79 GB at 13 layers."""
    n_dense, n_moe = layers(model)
    m = W.dims(model)
    moe_layer = attention_matmul_params(model) + (m["Eh"] + m["ns"]) * expert_params(model)
    body = n_dense * layer_matmul_params(model, False) + n_moe * moe_layer
    return int(body + 2 * m["vocab"] * m["d"]) * weight_dtype_bytes + n_moe * m["d"] * m["E"] * 4


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
    """The held experts' least time for `pairs` (token, expert) rows computed
    here over `touched` of the held experts (summed over layer calls): the
    larger of streaming each touched expert's weights once and of the pairs'
    FLOPs at the bf16 peak."""
    stream = touched * expert_params(model) * weight_dtype_bytes / pk["hbm_bytes_per_s"]
    flops = pairs * 2 * expert_params(model) / pk["bf16_flops"]
    return max(stream, flops)

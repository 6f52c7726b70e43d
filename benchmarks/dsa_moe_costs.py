"""The dsa_moe family's operations and bytes, from the configuration's sizes
(families/dsa_moe.py): the yardstick of every share of a peak in its cells.
A token's OWN work is counted, whatever the program computes beside it: the
indexer over its whole causal context, latent attention over the
min(index_topk, context) rows it chose, of its top-k routed experts the
pairs computed HERE (k x held / routed on average), the shared expert, the
router, the sliced head. Worked out by hand for DeepSeek-V3.2's cut beside
each: 7,168 wide, 128 heads, latent 512 + rope 64, indexer 64 heads of 128,
top-2,048, 16 of 256 experts of 2,048, 8 layers (1 dense)."""

from __future__ import annotations

import latent_moe_costs as LC

import dsa_moe_weights as W

ROPE_LANES = 128  # the rope key's columns as the cache keeps them: whole 128-lane rows


def attention_matmul_params(model: dict) -> int:
    """Weights one token multiplies in one layer's latent attention: 7168 x 1536
    + 1536 x 24576 + 7168 x 576 + 512 x 32768 + 16384 x 7168 = 187.1 M."""
    return sum(a * b for a, b in W.attention_shapes(model).values())


def indexer_params(model: dict) -> int:
    """The lightning indexer's projections: 1536 x 8192 + 7168 x 128 + 7168 x 64 = 14.0 M."""
    return sum(a * b for a, b in W.indexer_shapes(model).values())


def expert_params(model: dict) -> int:
    """One expert: gate, up, down (3 x 7168 x 2048 = 44.04 M)."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def pairs_here_per_token(model: dict) -> float:
    """Of a token's top-k pairs, those whose expert is held here, on average (8 x 16 / 256 = 0.5)."""
    m = W.dims(model)
    return m["k"] * m["Eh"] / m["E"]


def layer_matmul_params(model: dict, moe: bool) -> float:
    """Weights one token multiplies in one layer: attention and the indexer,
    then the dense FFN (201.1 + 396.4 = 597.5 M), or its pairs computed here,
    the shared expert and the router (201.1 + 1.5 x 44.04 + 1.84 = 269.0 M)."""
    attn = attention_matmul_params(model) + indexer_params(model)
    if not moe:
        return attn + 3 * model["hidden_size"] * model["intermediate_size"]
    m = W.dims(model)
    return attn + (pairs_here_per_token(model) + m["ns"]) * expert_params(model) + m["d"] * m["E"]


layers = LC.layers


def body_matmul_flops_per_token(model: dict) -> float:
    n_dense, n_moe = layers(model)
    return 2 * (n_dense * layer_matmul_params(model, False) + n_moe * layer_matmul_params(model, True))


head_flops_per_logit_row = LC.head_flops_per_logit_row


def chosen(model: dict, context: int) -> int:
    """Rows a query at `context` attends: min(index_topk, context)."""
    return min(int(model["index_topk"]), int(context))


def index_flops_per_row(model: dict) -> int:
    """The indexer's work a context row: every head's dot (2 x 64 x 128 = 16,384)."""
    return 2 * model["index_n_heads"] * model["index_head_dim"]


def sparse_flops_per_row(model: dict) -> int:
    """Latent attention a chosen row, absorbed: heads x (576 for the score +
    512 for the weighted sum) x 2 = 278,528."""
    return LC.attention_flops({**model, "num_hidden_layers": 1}, 1)


def attention_flops(model: dict, context: int) -> int:
    """One query token at `context`, all layers: the indexer over the whole
    context and attention over the rows it chose."""
    per_layer = index_flops_per_row(model) * context + sparse_flops_per_row(model) * chosen(model, context)
    return per_layer * model["num_hidden_layers"]


def row_bytes(model: dict, kv_dtype_bytes: int = 2) -> int:
    """A cache row as kept: latent 512, rope 64 padded to 128, index key 128
    (768 x 2 = 1,536 B a token a layer)."""
    return (model["kv_lora_rank"] + ROPE_LANES + model["index_head_dim"]) * kv_dtype_bytes


def index_key_bytes(model: dict, kv_dtype_bytes: int = 2) -> int:
    """A row's index key (128 x 2 = 256 B)."""
    return model["index_head_dim"] * kv_dtype_bytes


def chosen_row_bytes(model: dict, kv_dtype_bytes: int = 2) -> int:
    """What attention reads of a chosen row: latent and rope ((512 + 128) x 2 = 1,280 B)."""
    return (model["kv_lora_rank"] + ROPE_LANES) * kv_dtype_bytes


def index_read_bytes(model: dict, contexts, kv_dtype_bytes: int = 2) -> int:
    """The index keys the decode tokens at `contexts` had to read: every row
    of each context in every layer (13.3k x 256 B x 8 layers = 27.2 MB a token)."""
    return model["num_hidden_layers"] * index_key_bytes(model, kv_dtype_bytes) * sum(int(c) for c in contexts)


def sparse_read_bytes(model: dict, contexts, kv_dtype_bytes: int = 2) -> int:
    """The chosen rows the decode tokens at `contexts` attended: min(2,048,
    context) x 1,280 B a layer (21.0 MB a token at 8 layers)."""
    return model["num_hidden_layers"] * chosen_row_bytes(model, kv_dtype_bytes) * sum(
        chosen(model, c) for c in contexts)


def decode_kv_read_bytes(model: dict, contexts, kv_dtype_bytes: int = 2) -> int:
    return index_read_bytes(model, contexts, kv_dtype_bytes) + sparse_read_bytes(model, contexts, kv_dtype_bytes)


def weight_bytes(model: dict, weight_dtype_bytes: int = 1) -> int:
    """Every resident matmul weight and both tables at the served width: the
    dense layer 597.5 MB, a routed layer's share 201.1 + 17 x 44.04 = 949.8 MB
    and its float32 router 7.3 MB, the tables 2 x 115.8 MB: 7.53 GB at 1 + 7 layers."""
    n_dense, n_moe = layers(model)
    m = W.dims(model)
    moe_layer = attention_matmul_params(model) + indexer_params(model) + (m["Eh"] + m["ns"]) * expert_params(model)
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
    """The held experts' least time for `pairs` rows over `touched` experts:
    the larger of streaming each touched expert once and of the pairs' FLOPs."""
    stream = touched * expert_params(model) * weight_dtype_bytes / pk["hbm_bytes_per_s"]
    flops = pairs * 2 * expert_params(model) / pk["bf16_flops"]
    return max(stream, flops)

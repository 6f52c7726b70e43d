"""Operations and bytes the algorithm needs, from the configuration's sizes.

The yardstick for every share of a peak: kept with the benchmark so that no
later PR can move it. `model` is the configuration file's `model` group
(the published config.json keys). Nothing here reads the program.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"device kind {device_kind!r} is not in benchmarks/peaks.json: "
                       "add it with its source, there is no default")
    return table[device_kind]


def head_dim(model: dict) -> int:
    return int(model.get("head_dim") or model["hidden_size"] // model["num_attention_heads"])


def layer_matmul_params(model: dict) -> int:
    """Weights one token multiplies in one layer: q, k|v, o, gate, up, down."""
    d, ff = model["hidden_size"], model["intermediate_size"]
    hq, hkv, hd = model["num_attention_heads"], model["num_key_value_heads"], head_dim(model)
    return d * hq * hd + d * 2 * hkv * hd + hq * hd * d + 3 * d * ff


def body_matmul_flops_per_token(model: dict) -> int:
    """2 FLOPs per weight, all layers; the output head is counted apart,
    because only positions that emit a token need logits."""
    return 2 * layer_matmul_params(model) * model["num_hidden_layers"]


def head_flops_per_logit_row(model: dict) -> int:
    return 2 * model["hidden_size"] * model["vocab_size"]


def attention_flops(model: dict, context: int) -> int:
    """One query token against `context` keys (itself included), all layers:
    QK^T and PV, 2 FLOPs each per head-dim element. A sliding window caps
    the keys a token can see."""
    window = int(model.get("sliding_window") or 0)
    keys = min(context, window) if window > 0 else context
    return 4 * model["num_attention_heads"] * head_dim(model) * keys * model["num_hidden_layers"]


def kv_bytes_per_token(model: dict, kv_dtype_bytes: int = 2) -> int:
    """K and V rows of one token, all layers (Qwen2-7B bf16: 57,344)."""
    return 2 * model["num_hidden_layers"] * model["num_key_value_heads"] * head_dim(model) * kv_dtype_bytes


def decode_kv_read_bytes(model: dict, contexts, kv_dtype_bytes: int = 2) -> int:
    """KV bytes the decode tokens had to read, whatever implements the read:
    each token reads the rows of its whole context (window-capped)."""
    window = int(model.get("sliding_window") or 0)
    per = kv_bytes_per_token(model, kv_dtype_bytes)
    return sum((min(c, window) if window > 0 else c) * per for c in contexts)


def weight_bytes(model: dict, weight_dtype_bytes: int = 1) -> int:
    """The matmul weights and both [vocab, d] tables at the served width."""
    body = layer_matmul_params(model) * model["num_hidden_layers"]
    tables = 2 * model["vocab_size"] * model["hidden_size"]
    return (body + tables) * weight_dtype_bytes


def least_step_seconds(model: dict, pk: dict, *, prefill_contexts, decode_contexts,
                       prefill_int8: bool = True) -> dict:
    """The least time the chip could take for these tokens: the matmul FLOPs
    of prefill tokens at the int8 peak (W8A8) when the configuration serves
    int8 weights, everything else (decode matmuls, every token's attention,
    one head row per emitted token) at the bf16 peak. `*_contexts` list each
    token's context length. Returns the parts and their sum."""
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

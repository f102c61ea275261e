"""Operations and bytes the algorithm needs, from a configuration's
published shapes (not the program's padded ones), whatever implements it.

Training counts the forward and backward passes, no recomputation:
``6 * matmul_params * tokens`` plus causal attention.  A decode step counts
each live sequence's projections and its attention over its real context,
and the bytes of the weights (read once a step, at the stored precision)
and of the live K/V at the key/value heads."""

from __future__ import annotations

DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def matmul_params(cfg: dict) -> int:
    """Weights that enter a matrix product for every token: the layers'
    projections and the output head (an embedding lookup is no product)."""
    d, f, hd = cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    layer = 2 * d * hq * hd + 2 * d * hkv * hd + 3 * d * f
    return cfg["num_hidden_layers"] * layer + d * cfg["vocab_size"]


def train_step_flops(cfg: dict, mix: dict) -> float:
    b, s = mix["global_batch"], mix["seq_len"]
    hq, hd = cfg["num_attention_heads"], cfg["head_dim"]
    dense = 6.0 * matmul_params(cfg) * b * s
    # causal: QK^T and PV over half the (s, s) square, 2 flops per MAC,
    # times 3 for forward + backward
    attn = 3 * 2 * 2 * (s * s / 2) * hq * hd * b * cfg["num_hidden_layers"]
    return dense + attn


def kv_bytes_per_token(cfg: dict) -> int:
    """K and V of one position, all layers, at the cache precision."""
    eb = DTYPE_BYTES[cfg["precision"].get("kv_cache", "bfloat16")]
    return (2 * cfg["num_key_value_heads"] * cfg["head_dim"] * eb
            * cfg["num_hidden_layers"])


def decode_step_need(cfg: dict, live: int, context: int) -> dict:
    """One decode step over ``live`` sequences holding ``context`` positions
    in all (each counted after this step's token is written)."""
    hq, hd, layers = (cfg["num_attention_heads"], cfg["head_dim"],
                      cfg["num_hidden_layers"])
    wb = DTYPE_BYTES[cfg["precision"]["params"]]
    attn_flops = 4.0 * hq * hd * context * layers
    kv = kv_bytes_per_token(cfg) * context
    # the kernel also reads q (bf16) and writes its fp32 output per head
    qo = live * layers * hq * hd * (2 + 4)
    return {
        "flops": 2.0 * matmul_params(cfg) * live + attn_flops,
        "bytes": wb * matmul_params(cfg) + kv,
        "attn_flops": attn_flops,
        "attn_bytes": kv + qo,
    }

"""Operations and bytes a decode step needs, for a decoder with multi-head
latent attention and expert layers (DeepSeek-V3's layers), from a
configuration's published shapes, whatever implements it.

Bytes: every weight that enters a matrix product, once a step at its
stored precision (the experts this chip holds, each of them, the router,
the shared experts and the output head; an embedding lookup reads rows,
not the table), plus the latent rows (``kv_lora_rank + qk_rope_head_dim``
values a token and layer) of the live sequences at their real lengths.
FLOPs: two per weight a token uses (every projection; of the held
experts, the share ``top_k * held / experts`` a token chooses on average)
and, per context token and layer, ``2 * heads * (kv_lora_rank +
qk_rope_head_dim)`` to score the latent row and ``2 * heads *
kv_lora_rank`` to weigh it."""

from __future__ import annotations

DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def _sizes(cfg: dict) -> dict:
    d, hh = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    r, f = cfg["kv_lora_rank"], cfg["moe_intermediate_size"]
    mla = d * hh * (dn + dr) + d * (r + dr) + r * hh * (dn + dv) + hh * dv * d
    return {
        "mla": mla,
        "dense_mlp": 3 * d * cfg["intermediate_size"],
        "expert": 3 * d * f,
        "shared": 3 * d * f * cfg["n_shared_experts"],
        "router": d * cfg["n_routed_experts"],
        "head": d * cfg["vocab_size"],
        "dense_layers": cfg["first_k_dense_replace"],
        "moe_layers": cfg["num_hidden_layers"] - cfg["first_k_dense_replace"],
    }


def weight_params(cfg: dict) -> int:
    """Weights that enter a matrix product in a decode step, each once
    (all the held experts of every expert layer)."""
    z = _sizes(cfg)
    moe = z["router"] + z["shared"] + cfg["num_experts_held"] * z["expert"]
    return (cfg["num_hidden_layers"] * z["mla"] + z["dense_layers"]
            * z["dense_mlp"] + z["moe_layers"] * moe + z["head"])


def token_params(cfg: dict) -> float:
    """Weights one token multiplies, on average (the held experts at the
    share a token chooses)."""
    z = _sizes(cfg)
    chosen = (cfg["num_experts_per_tok"] * cfg["num_experts_held"]
              / cfg["n_routed_experts"])
    moe = z["router"] + z["shared"] + chosen * z["expert"]
    return (cfg["num_hidden_layers"] * z["mla"] + z["dense_layers"]
            * z["dense_mlp"] + z["moe_layers"] * moe + z["head"])


def latent_bytes_per_token(cfg: dict) -> int:
    """One position's latent row and RoPE key, all layers, at the cache
    precision."""
    eb = DTYPE_BYTES[cfg["precision"].get("kv_cache", "bfloat16")]
    return ((cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * eb
            * cfg["num_hidden_layers"])


def decode_step_need(cfg: dict, live: int, context: int) -> dict:
    """One decode step over ``live`` sequences holding ``context`` positions
    in all (each counted after this step's token is written)."""
    hh, r, dr, layers = (cfg["num_attention_heads"], cfg["kv_lora_rank"],
                         cfg["qk_rope_head_dim"], cfg["num_hidden_layers"])
    wb = DTYPE_BYTES[cfg["precision"]["params"]]
    attn_flops = 2.0 * hh * ((r + dr) + r) * context * layers
    kv = latent_bytes_per_token(cfg) * context
    # the kernel also reads each head's query (r + dr, bf16) and writes its
    # fp32 weighted latent row
    qo = live * layers * hh * ((r + dr) * 2 + r * 4)
    return {
        "flops": 2.0 * token_params(cfg) * live + attn_flops,
        "bytes": wb * weight_params(cfg) + kv,
        "attn_flops": attn_flops,
        "attn_bytes": kv + qo,
    }

"""Multi-head latent attention (DeepSeek-V2/V3 MLA, no query LoRA).

Per token ``h`` (d_model), with ``H`` heads, no-RoPE width ``dn``
(``AttnConfig.head_dim``), RoPE width ``dr``, value width ``dv`` and
latent rank ``r``::

    [q_nope_h | q_pe_h] = (W_q h)_h                     # dn | dr per head
    [c | k_pe]          = W_kva h                        # r | dr
    c    = RMSNorm(c)
    k_pe = RoPE(k_pe)                                    # one, for all heads
    q_pe = RoPE(q_pe)
    [k_nope_h | v_h]    = (W_kvb c)_h                    # dn | dv per head
    score_h(t) = (q_nope_h . k_nope_h(t) + q_pe_h . k_pe(t)) / sqrt(dn + dr)
    y = W_o concat_h(softmax(score_h) . v_h)

RoPE follows the published modelling code: the ``dr`` dims are read as
interleaved pairs ``(x[2i], x[2i+1])``, regrouped as ``[evens | odds]``,
then rotated half against half at frequency ``theta^(-2i/dr)``.

The full-sequence path (training, prefill) expands ``k_nope`` and ``v``
per head from the latent rows.  Decode keeps only the latent rows ``c``
and the RoPE keys ``k_pe`` (``r + dr`` values a token, shared by every
head) and scores them in the absorbed form: ``q_c,h = W_UK,h^T q_nope,h``
(``r`` wide) so that ``q_nope,h . k_nope,h(t) = q_c,h . c(t)``, and the
heads' attention-weighted latent rows ``o_c,h = sum_t p_h(t) c(t)`` pass
through ``W_UV,h`` afterwards.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import AttnConfig
from repro.models.attention import NEG_INF, blockwise_attention
from repro.models.common import (apply_rope, dense, dense_init, rmsnorm,
                                 rmsnorm_init)


def mla_init(key, cfg: AttnConfig, d_model: int, *, dtype=jnp.float32) -> dict:
    h, dn, dr = cfg.num_heads, cfg.head_dim, cfg.qk_rope_head_dim
    dv, r = cfg.v_head_dim, cfg.kv_lora_rank
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "q_proj": dense_init(k1, d_model, h * (dn + dr), dtype=dtype),
        "kv_a_proj": dense_init(k2, d_model, r + dr, dtype=dtype),
        "kv_a_norm": rmsnorm_init(r, dtype),
        "kv_b_proj": dense_init(k3, r, h * (dn + dv), dtype=dtype),
        "o_proj": dense_init(k4, h * dv, d_model, dtype=dtype),
    }


def rope_pairs(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, H, S, dr).  Interleaved pairs regrouped ``[evens | odds]``,
    then rotate-half RoPE (the published modelling code's layout)."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    return apply_rope(x, positions, theta)


def _kv_b(p: dict, cfg: AttnConfig) -> tuple[jax.Array, jax.Array]:
    """``W_UK`` (r, H, dn) and ``W_UV`` (r, H, dv) of ``kv_b_proj``."""
    w = p["kv_b_proj"]["w"].reshape(cfg.kv_lora_rank, cfg.num_heads,
                                    cfg.head_dim + cfg.v_head_dim)
    return w[..., :cfg.head_dim], w[..., cfg.head_dim:]


def scale(cfg: AttnConfig) -> float:
    return 1.0 / math.sqrt(cfg.head_dim + cfg.qk_rope_head_dim)


def latent(p: dict, h: jax.Array, cfg: AttnConfig, positions, eps: float,
           compute_dtype) -> tuple[jax.Array, jax.Array]:
    """h: (B, S, d) -> the normed latent rows ``c`` (B, S, r) and the roped
    shared keys ``k_pe`` (B, S, dr)."""
    r = cfg.kv_lora_rank
    kv = dense(p["kv_a_proj"], h, compute_dtype)
    c = rmsnorm(p["kv_a_norm"], kv[..., :r], eps)
    k_pe = rope_pairs(kv[:, None, :, r:], positions, cfg.rope_theta)[:, 0]
    return c, k_pe


def query(p: dict, h: jax.Array, cfg: AttnConfig, positions,
          compute_dtype) -> tuple[jax.Array, jax.Array]:
    """h: (B, S, d) -> ``q_nope`` (B, H, S, dn) and roped ``q_pe``
    (B, H, S, dr)."""
    b, s, _ = h.shape
    dn = cfg.head_dim
    q = dense(p["q_proj"], h, compute_dtype).reshape(b, s, cfg.num_heads, -1)
    q = q.transpose(0, 2, 1, 3)
    return q[..., :dn], rope_pairs(q[..., dn:], positions, cfg.rope_theta)


def mla_apply(p: dict, x: jax.Array, cfg: AttnConfig, *, eps: float,
              positions=None, compute_dtype=jnp.bfloat16,
              causal_skip: bool = False) -> jax.Array:
    """Causal MLA over a full sequence, keys and values expanded per head
    from the latent rows.  x: (B, S, d)."""
    b, s, _ = x.shape
    hh, dn, dv = cfg.num_heads, cfg.head_dim, cfg.v_head_dim
    pos = positions if positions is not None else jnp.arange(s)
    q_nope, q_pe = query(p, x, cfg, pos, compute_dtype)
    c, k_pe = latent(p, x, cfg, pos, eps, compute_dtype)
    kv = dense(p["kv_b_proj"], c, compute_dtype).reshape(b, s, hh, dn + dv)
    kv = kv.transpose(0, 2, 1, 3)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_pe[:, None], (b, hh, s,
                                                         k_pe.shape[-1]))],
        axis=-1)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    o = blockwise_attention(q, k, kv[..., dn:], causal=True,
                            causal_skip=causal_skip)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, hh * dv)
    return dense(p["o_proj"], o, compute_dtype)


def absorb_query(p: dict, q_nope: jax.Array, cfg: AttnConfig,
                 compute_dtype) -> jax.Array:
    """``q_c,h = W_UK,h^T q_nope,h``: (B, H, dn) -> (B, H, r) float32."""
    w_uk, _ = _kv_b(p, cfg)
    return jnp.einsum("bhn,rhn->bhr", q_nope.astype(compute_dtype),
                      w_uk.astype(compute_dtype),
                      preferred_element_type=jnp.float32)


def latent_out(p: dict, o_c: jax.Array, cfg: AttnConfig,
               compute_dtype) -> jax.Array:
    """The heads' weighted latent rows (B, H, r) through ``W_UV`` and
    ``W_o``: (B, d)."""
    _, w_uv = _kv_b(p, cfg)
    o = jnp.einsum("bhr,rhv->bhv", o_c.astype(compute_dtype),
                   w_uv.astype(compute_dtype),
                   preferred_element_type=jnp.float32)
    return dense(p["o_proj"], o.reshape(o.shape[0], -1), compute_dtype)


def latent_stats(q_c, q_pe, c, k_pe, valid, sc: float):
    """Partial softmax statistics of the absorbed scores over latent rows.

    q_c: (B, H, r), q_pe: (B, H, dr); c: (B, L, r), k_pe: (B, L, dr);
    valid: (B, L).  Returns fp32 ``(acc (B, H, r), m (B, H, 1),
    l (B, H, 1))``, the unnormalised weighted sum of ``c``."""
    f32 = jnp.float32
    s = (jnp.einsum("bhr,blr->bhl", q_c.astype(f32), c.astype(f32))
         + jnp.einsum("bhe,ble->bhl", q_pe.astype(f32), k_pe.astype(f32)))
    s = jnp.where(valid[:, None, :], s * sc, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    return (jnp.einsum("bhl,blr->bhr", e, c.astype(f32)), m,
            jnp.sum(e, axis=-1, keepdims=True))


def mla_decode(p: dict, x1: jax.Array, cfg: AttnConfig, cache: dict, *,
               pos: jax.Array, eps: float, compute_dtype=jnp.bfloat16
               ) -> tuple[jax.Array, dict]:
    """One-token decode against a contiguous latent cache, absorbed form.
    ``cache``: {"c": (B, C, r), "k_pe": (B, C, dr)}; ``pos`` (scalar) is
    this token's position."""
    posv = jnp.asarray(pos)
    pos1 = posv.reshape(1)
    q_nope, q_pe = query(p, x1, cfg, pos1, compute_dtype)
    c1, k1 = latent(p, x1, cfg, pos1, eps, compute_dtype)
    c = jax.lax.dynamic_update_slice_in_dim(
        cache["c"], c1.astype(cache["c"].dtype), posv, axis=1)
    k_pe = jax.lax.dynamic_update_slice_in_dim(
        cache["k_pe"], k1.astype(cache["k_pe"].dtype), posv, axis=1)
    q_c = absorb_query(p, q_nope[:, :, 0], cfg, compute_dtype)
    valid = jnp.broadcast_to(jnp.arange(c.shape[1]) <= posv,
                             (c.shape[0], c.shape[1]))
    acc, _, l = latent_stats(q_c, q_pe[:, :, 0], c, k_pe, valid, scale(cfg))
    y = latent_out(p, acc / l, cfg, compute_dtype)
    return y[:, None].astype(x1.dtype), {"c": c, "k_pe": k_pe}


def init_cache(cfg: AttnConfig, batch: int, seq_len: int,
               dtype=jnp.bfloat16) -> dict:
    return {"c": jnp.zeros((batch, seq_len, cfg.kv_lora_rank), dtype),
            "k_pe": jnp.zeros((batch, seq_len, cfg.qk_rope_head_dim), dtype)}

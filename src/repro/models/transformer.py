"""Decoder-only transformer assembly covering dense / MoE / SSM / hybrid
families, with unrolled layers, per-layer remat, KV/SSM decode state, and
modality-stub extra embeddings (VLM patches, audio frames).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.common import (dense_init, embed, embed_init, glu_mlp,
                                 glu_mlp_init, rmsnorm, rmsnorm_init,
                                 softmax_xent, unembed)

AUX_LOSS_WEIGHT = 0.01


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def block_init(key, cfg: ModelConfig, i: int, dtype) -> dict:
    kind = cfg.layer_kind(i)
    ks = jax.random.split(key, 4)
    p: dict = {"ln1": rmsnorm_init(cfg.d_model, dtype),
               "ln2": rmsnorm_init(cfg.d_model, dtype)}
    if kind["mixer"] == "attn" and cfg.attn.is_mla:
        p["mla"] = mla_mod.mla_init(ks[0], cfg.attn, cfg.d_model, dtype=dtype)
    elif kind["mixer"] in ("attn", "hybrid"):
        p["attn"] = attn_mod.attn_init(ks[0], cfg.attn, cfg.d_model, dtype=dtype)
    if kind["mixer"] in ("ssm", "hybrid"):
        p["ssm"] = ssm_mod.ssm_init(ks[1], cfg.ssm, cfg.d_model, dtype=dtype)
    if kind["mixer"] == "hybrid":
        p["beta"] = jnp.ones((2,), dtype)
    if kind["mlp"] == "moe":
        p["moe"] = moe_mod.moe_init(ks[2], cfg.moe, cfg.d_model, dtype=dtype)
    elif cfg.d_ff > 0:
        p["mlp"] = glu_mlp_init(ks[3], cfg.d_model, cfg.d_ff, dtype=dtype)
    return p


def init_params(key, cfg: ModelConfig) -> dict:
    dtype = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, cfg.num_layers + 2)
    params = {
        "embed": embed_init(ks[0], cfg.vocab_size, cfg.d_model, dtype=dtype),
        "blocks": [block_init(ks[1 + i], cfg, i, dtype)
                   for i in range(cfg.num_layers)],
        "final_norm": rmsnorm_init(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[-1], cfg.d_model, cfg.vocab_size,
                                       dtype=dtype)
    return params


def param_count(params) -> int:
    return sum(int(jnp.size(l)) for l in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------


def block_apply(p: dict, x: jax.Array, cfg: ModelConfig, i: int, *, ctx,
                positions, causal_skip: bool
                ) -> tuple[jax.Array, jax.Array, jax.Array]:
    kind = cfg.layer_kind(i)
    cdt = jnp.dtype(cfg.dtype)
    aux = jnp.zeros((), jnp.float32)
    drop = jnp.zeros((), jnp.float32)
    h = rmsnorm(p["ln1"], x, cfg.norm_eps)
    if "mla" not in p:   # MLA weights stay whole: no model-axis boundary
        h = ctx.fan_out(h)
    if "mla" in p:
        mix = mla_mod.mla_apply(p["mla"], h, cfg.attn, eps=cfg.norm_eps,
                                positions=positions, compute_dtype=cdt,
                                causal_skip=causal_skip)
    elif kind["mixer"] == "attn":
        mix = attn_mod.attn_apply(p["attn"], h, cfg.attn,
                                  is_global=kind.get("attn_global", True),
                                  ctx=ctx, positions=positions,
                                  compute_dtype=cdt, causal_skip=causal_skip)
    elif kind["mixer"] == "ssm":
        mix = ssm_mod.ssm_apply(p["ssm"], h, cfg.ssm, ctx=ctx,
                                compute_dtype=cdt, d_model=cfg.d_model)
    else:  # hybrid: parallel attention + SSM heads on the same input
        a = attn_mod.attn_apply(p["attn"], h, cfg.attn,
                                is_global=kind.get("attn_global", False),
                                ctx=ctx, positions=positions,
                                compute_dtype=cdt, causal_skip=causal_skip)
        s = ssm_mod.ssm_apply(p["ssm"], h, cfg.ssm, ctx=ctx,
                              compute_dtype=cdt, d_model=cfg.d_model)
        beta = p["beta"].astype(cdt)
        mix = 0.5 * (a * beta[0] + s * beta[1])
    x = x + mix.astype(x.dtype)

    if "moe" not in p and "mlp" not in p:     # pure-SSM stacks (d_ff == 0)
        return x, aux, drop
    h = rmsnorm(p["ln2"], x, cfg.norm_eps)
    if kind["mlp"] != "moe":      # moe places its own f-boundaries
        h = ctx.fan_out(h)
    if kind["mlp"] == "moe":
        y, aux, drop = moe_mod.moe_apply(p["moe"], h, cfg.moe, cfg.act,
                                         ctx=ctx, compute_dtype=cdt)
    else:
        y = glu_mlp(p["mlp"], h, cfg.act, cdt, ctx, cfg.d_ff)
    return x + y.astype(x.dtype), aux, drop


def forward(params: dict, tokens: jax.Array, cfg: ModelConfig, *, ctx,
            extra_embeds: jax.Array | None = None,
            causal_skip: bool = False,
            block_resolver=None) -> tuple[jax.Array, jax.Array, jax.Array]:
    """tokens: (B, S_text).  ``extra_embeds`` (B, P, d) are prepended
    (modality stub).  Returns (logits (B, S_total, V_local), aux_loss,
    drop_fraction) — the latter averaged over the MoE layers (0 for dense
    stacks)."""
    cdt = jnp.dtype(cfg.dtype)
    x = embed(params["embed"], tokens, cdt, ctx, cfg.vocab_size)
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(cdt), x], axis=1)
    positions = jnp.arange(x.shape[1])
    aux_total = jnp.zeros((), jnp.float32)
    drop_total = jnp.zeros((), jnp.float32)
    n_moe = sum(1 for i in range(cfg.num_layers)
                if cfg.layer_kind(i)["mlp"] == "moe")

    for i, raw in enumerate(params["blocks"]):
        # ``raw`` is either the block's param dict or (FSDP) its flat shard
        # list; the resolver ring-all-gathers INSIDE the remat boundary so
        # backward re-gathers instead of pinning gathered weights.
        def fn(p_, x_, i_=i):
            bp = block_resolver("blocks", i_, p_) if block_resolver else p_
            return block_apply(bp, x_, cfg, i_, ctx=ctx, positions=positions,
                               causal_skip=causal_skip)
        if cfg.remat == "layer":
            fn = jax.checkpoint(fn)
        x, aux, drop = fn(raw, x)
        aux_total = aux_total + aux
        drop_total = drop_total + drop

    x = ctx.fan_out(rmsnorm(params["final_norm"], x, cfg.norm_eps))
    if cfg.tie_embeddings:
        logits = unembed(params["embed"], x, cdt)
    else:
        from repro.models.common import dense

        logits = dense(params["lm_head"], x, cdt)
    return logits, aux_total, drop_total / max(n_moe, 1)


def loss_fn(params: dict, batch: dict, cfg: ModelConfig, *, ctx,
            causal_skip: bool = False, block_resolver=None,
            stats_out: list | None = None) -> jax.Array:
    """batch: {"tokens": (B,S), "labels": (B,S), optional "mask",
    optional "extra_embeds" (B,P,d)} — loss over text positions only.

    ``stats_out``, when given, receives one ``{"moe_drop_fraction": scalar}``
    dict per call — the side channel the train step uses to surface routing
    health without changing the loss signature ``value_and_grad`` sees."""
    extra = batch.get("extra_embeds")
    logits, aux, drop = forward(params, batch["tokens"], cfg, ctx=ctx,
                                extra_embeds=extra, causal_skip=causal_skip,
                                block_resolver=block_resolver)
    if extra is not None:
        logits = logits[:, extra.shape[1]:]
    loss = softmax_xent(logits, batch["labels"], batch.get("mask"), ctx,
                        cfg.vocab_size)
    if stats_out is not None:
        stats_out.append({"moe_drop_fraction": drop})
    return loss + AUX_LOSS_WEIGHT * aux


# ---------------------------------------------------------------------------
# decode (single token against running state)
# ---------------------------------------------------------------------------


def init_decode_state(cfg: ModelConfig, batch: int, seq_len: int,
                      cache_dtype=jnp.bfloat16) -> list:
    state = []
    for i in range(cfg.num_layers):
        kind = cfg.layer_kind(i)
        st: dict = {}
        if kind["mixer"] == "attn" and cfg.attn.is_mla:
            st["latent"] = mla_mod.init_cache(cfg.attn, batch, seq_len,
                                              dtype=cache_dtype)
        elif kind["mixer"] in ("attn", "hybrid"):
            st["kv"] = attn_mod.init_cache(cfg.attn, batch, seq_len,
                                           is_global=kind.get("attn_global",
                                                              kind["mixer"] == "attn"),
                                           dtype=cache_dtype)
        if kind["mixer"] in ("ssm", "hybrid"):
            st["ssm"] = ssm_mod.init_ssm_state(cfg.ssm, cfg.d_model, batch,
                                               dtype=jnp.float32)
        state.append(st)
    return state


def cache_len(cfg: ModelConfig, i: int, seq_len: int) -> int:
    """Global KV-cache length for layer ``i`` (mirrors init_cache)."""
    kind = cfg.layer_kind(i)
    is_global = kind.get("attn_global", kind["mixer"] == "attn")
    c = seq_len
    if not is_global and cfg.attn is not None:
        if cfg.attn.window is not None:
            c = min(c, cfg.attn.window)
        elif cfg.attn.chunk is not None:
            c = min(c, cfg.attn.chunk)
    return c


def decode_step(params: dict, token: jax.Array, state: list, pos: jax.Array,
                cfg: ModelConfig, *, ctx, seq_len: int | None = None,
                block_resolver=None) -> tuple[jax.Array, list]:
    """token: (B,) ints; returns (local-vocab logits (B, V_l), new_state)."""
    cdt = jnp.dtype(cfg.dtype)
    x = embed(params["embed"], token[:, None], cdt, ctx, cfg.vocab_size)
    new_state = []
    for i, raw in enumerate(params["blocks"]):
        bp = block_resolver("blocks", i, raw) if block_resolver else raw
        kind = cfg.layer_kind(i)
        st = dict(state[i])
        h = rmsnorm(bp["ln1"], x, cfg.norm_eps)
        clen = cache_len(cfg, i, seq_len) if seq_len else None
        if "mla" in bp:
            mix, st["latent"] = mla_mod.mla_decode(
                bp["mla"], h, cfg.attn, st["latent"], pos=pos,
                eps=cfg.norm_eps, compute_dtype=cdt)
        elif kind["mixer"] == "attn":
            mix, st["kv"] = attn_mod.attn_decode(
                bp["attn"], h, cfg.attn, st["kv"],
                is_global=kind.get("attn_global", True), ctx=ctx, pos=pos,
                compute_dtype=cdt, cache_len_global=clen)
        elif kind["mixer"] == "ssm":
            mix, st["ssm"] = ssm_mod.ssm_decode(bp["ssm"], h, cfg.ssm,
                                                st["ssm"], ctx=ctx,
                                                compute_dtype=cdt,
                                                d_model=cfg.d_model)
        else:
            a, st["kv"] = attn_mod.attn_decode(
                bp["attn"], h, cfg.attn, st["kv"],
                is_global=kind.get("attn_global", False), ctx=ctx, pos=pos,
                compute_dtype=cdt, cache_len_global=clen)
            s, st["ssm"] = ssm_mod.ssm_decode(bp["ssm"], h, cfg.ssm,
                                              st["ssm"], ctx=ctx,
                                              compute_dtype=cdt,
                                              d_model=cfg.d_model)
            beta = bp["beta"].astype(cdt)
            mix = 0.5 * (a * beta[0] + s * beta[1])
        x = x + mix.astype(x.dtype)
        if "moe" in bp or "mlp" in bp:
            h = rmsnorm(bp["ln2"], x, cfg.norm_eps)
            if kind["mlp"] == "moe":
                y, _, _ = moe_mod.moe_apply(bp["moe"], h, cfg.moe, cfg.act,
                                            ctx=ctx, compute_dtype=cdt)
            else:
                y = glu_mlp(bp["mlp"], h, cfg.act, cdt, ctx, cfg.d_ff)
            x = x + y.astype(x.dtype)
        new_state.append(st)

    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = unembed(params["embed"], x, cdt)
    else:
        from repro.models.common import dense

        logits = dense(params["lm_head"], x, cdt)
    return logits[:, 0], new_state

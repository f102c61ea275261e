"""GQA attention: blockwise training path, flash-kernel prefill, cached decode.

Masking flavours cover the assigned archs: full causal, sliding-window
(mixtral/hymba), and chunked-local (llama4 iRoPE-style).  Query heads are
zero-padded up to a multiple of ``head_pad_to`` so tensor parallelism tiles
the mesh's model axis exactly (the framework *guarantees* shardability —
the paper's determinism ethos; the pad is recorded in the param count).

The training path blocks over both q and kv in unrolled python loops with an
fp32 online softmax: differentiable, bounded VMEM/HBM working set, and —
because the loops are unrolled — honestly counted by the dry-run cost
analysis.  ``causal_skip`` statically skips fully-masked (future) kv blocks,
halving attention FLOPs; it is OFF by default so §Perf can show the
before/after.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import AttnConfig
from repro.models.common import apply_rope, dense, dense_init
from repro.models.parallel import sum_grads_over_model

NEG_INF = -1e30
HEAD_PAD_TO = 16  # model-axis size the padded head count must tile


def padded_heads(n: int, pad_to: int = HEAD_PAD_TO) -> int:
    return int(math.ceil(n / pad_to) * pad_to)


def attn_init(key, cfg: AttnConfig, d_model: int, *, dtype=jnp.float32,
              pad_to: int = HEAD_PAD_TO) -> dict:
    """Query heads are zero-padded to tile the model axis; the padded rows of
    ``wo`` are zero so padded heads never influence the output.  KV heads are
    never padded (they replicate across TP ranks; each rank gathers the kv
    heads its local q heads group to)."""
    hq = padded_heads(cfg.num_heads, pad_to)
    hkv = cfg.num_kv_heads
    hd = cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    wo = dense_init(k4, hq * hd, d_model, dtype=dtype)
    if hq > cfg.num_heads:
        wo["w"] = wo["w"].at[cfg.num_heads * hd:].set(0.0)
    return {
        "wq": dense_init(k1, d_model, hq * hd, dtype=dtype, bias=cfg.qkv_bias),
        "wk": dense_init(k2, d_model, hkv * hd, dtype=dtype, bias=cfg.qkv_bias),
        "wv": dense_init(k3, d_model, hkv * hd, dtype=dtype, bias=cfg.qkv_bias),
        "wo": wo,
    }


def _gather_kv_for_local_q(k: jax.Array, v: jax.Array, cfg: AttnConfig,
                           hq_local: int, ctx):
    """TP rank-local GQA mapping: q head ``h`` (global) reads kv head
    ``h // true_group`` (clipped for padded heads).  Returns per-q-head kv."""
    true_group = max(cfg.num_heads // cfg.num_kv_heads, 1)
    h_global = ctx.model_index() * hq_local + jnp.arange(hq_local)
    kv_idx = jnp.clip(h_global // true_group, 0, cfg.num_kv_heads - 1)
    return jnp.take(k, kv_idx, axis=1), jnp.take(v, kv_idx, axis=1)


def _split_heads(x: jax.Array, n_heads: int) -> jax.Array:
    b, s, _ = x.shape
    return x.reshape(b, s, n_heads, -1).transpose(0, 2, 1, 3)


def _merge_heads(x: jax.Array) -> jax.Array:
    b, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def _mask(q_pos: jax.Array, k_pos: jax.Array, *, causal: bool,
          window: int | None, chunk: int | None) -> jax.Array:
    m = jnp.ones(jnp.broadcast_shapes(q_pos.shape, k_pos.shape), bool)
    if causal:
        m &= k_pos <= q_pos
    if window is not None:
        m &= k_pos > q_pos - window
    if chunk is not None:
        m &= (k_pos // chunk) == (q_pos // chunk)
    return m


# ---------------------------------------------------------------------------
# blockwise attention (training / prefill; differentiable)
# ---------------------------------------------------------------------------


def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, window: int | None = None,
                        chunk: int | None = None, block_q: int = 2048,
                        block_k: int = 2048, causal_skip: bool = False) -> jax.Array:
    """q/k: (B,Hq|Hkv,S,D), v: (B,Hkv,S,Dv).  Online-softmax over kv
    blocks; the output is (B,Hq,S,Dv)."""
    b, hq, sq, d = q.shape
    dv = v.shape[-1]
    hkv, sk = k.shape[1], k.shape[2]
    group = hq // hkv
    scale = 1.0 / math.sqrt(d)
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    nq = math.ceil(sq / bq)
    nk = math.ceil(sk / bk)

    outs = []
    for i in range(nq):
        q0, q1 = i * bq, min((i + 1) * bq, sq)
        qi = q[:, :, q0:q1].astype(jnp.float32) * scale
        m = jnp.full((b, hq, q1 - q0, 1), NEG_INF, jnp.float32)
        l = jnp.zeros((b, hq, q1 - q0, 1), jnp.float32)
        acc = jnp.zeros((b, hq, q1 - q0, dv), jnp.float32)
        for j in range(nk):
            k0, k1_ = j * bk, min((j + 1) * bk, sk)
            if causal_skip and causal and k0 > q1 - 1:
                continue  # statically future-only block: zero contribution
            if causal_skip and window is not None and k1_ - 1 <= q0 - window:
                continue  # statically out-of-window block
            if causal_skip and chunk is not None and (k1_ - 1) // chunk < q0 // chunk:
                continue  # statically before this q-range's first chunk
            kj = k[:, :, k0:k1_].astype(jnp.float32)
            vj = v[:, :, k0:k1_].astype(jnp.float32)
            if group > 1:
                kj = jnp.repeat(kj, group, axis=1)
                vj = jnp.repeat(vj, group, axis=1)
            s = jnp.einsum("bhqd,bhkd->bhqk", qi, kj)
            q_pos = jnp.arange(q0, q1)[:, None]
            k_pos = jnp.arange(k0, k1_)[None, :]
            msk = _mask(q_pos, k_pos, causal=causal, window=window, chunk=chunk)
            s = jnp.where(msk[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + jnp.einsum("bhqk,bhkd->bhqd", p, vj)
            m = m_new
        outs.append(acc / jnp.maximum(l, 1e-30))
    return jnp.concatenate(outs, axis=2).astype(q.dtype)


# ---------------------------------------------------------------------------
# cached single-token decode
# ---------------------------------------------------------------------------


def decode_attention(q1: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     pos: jax.Array, *, window: int | None = None,
                     chunk: int | None = None, rolling: bool = False) -> jax.Array:
    """q1: (B,Hq,1,D); caches: (B,Hkv,C,D); ``pos``: current position (scalar).

    With ``rolling`` the cache is a circular buffer of size C holding the
    last C positions; slot ``t`` holds absolute position
    ``pos - ((pos - t) mod C)`` — masking handles validity.
    """
    b, hq, _, d = q1.shape
    hkv, c = k_cache.shape[1], k_cache.shape[2]
    group = hq // hkv
    k = jnp.repeat(k_cache, group, axis=1) if group > 1 else k_cache
    v = jnp.repeat(v_cache, group, axis=1) if group > 1 else v_cache
    s = jnp.einsum("bhqd,bhkd->bhqk", q1.astype(jnp.float32) / math.sqrt(d),
                   k.astype(jnp.float32))
    slot = jnp.arange(c)
    if rolling:
        delta = jnp.mod(pos - slot, c)          # age of each slot
        k_pos = pos - delta
    else:
        k_pos = slot
    valid = (k_pos <= pos) & (k_pos >= 0)       # >=0 excludes unwritten slots
    if window is not None:
        valid &= k_pos > pos - window
    if chunk is not None:
        valid &= (k_pos // chunk) == (pos // chunk)
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return out.astype(q1.dtype)


# ---------------------------------------------------------------------------
# full layer
# ---------------------------------------------------------------------------


def _needs_psum(p: dict, cfg: AttnConfig) -> bool:
    """Row-parallel wo: psum iff the merged-head dim is a local shard."""
    return p["wo"]["w"].shape[0] < padded_heads(cfg.num_heads) * cfg.head_dim


def attn_apply(p: dict, x: jax.Array, cfg: AttnConfig, *, is_global: bool,
               ctx, positions: jax.Array | None = None,
               compute_dtype=jnp.bfloat16, causal: bool = True,
               causal_skip: bool = False, cross_kv: jax.Array | None = None,
               block_q: int = 2048, block_k: int = 2048) -> jax.Array:
    """Self (or cross) attention over a full sequence (train / prefill).
    Weights may be local TP shards; ``ctx.psum`` completes the row-parallel
    output projection."""
    b, s, _ = x.shape
    hq = p["wq"]["w"].shape[1] // cfg.head_dim
    hkv = p["wk"]["w"].shape[1] // cfg.head_dim
    q = _split_heads(dense(p["wq"], x, compute_dtype), hq)
    kv_src = cross_kv if cross_kv is not None else x
    tp_kv = hq < padded_heads(cfg.num_heads)   # TP-sharded q, replicated kv
    wk, wv = p["wk"], p["wv"]
    if tp_kv:
        # kv use is rank-dependent (head gather): sum grads over model axis
        wk = sum_grads_over_model(wk, ctx)
        wv = sum_grads_over_model(wv, ctx)
    k = _split_heads(dense(wk, kv_src, compute_dtype), hkv)
    v = _split_heads(dense(wv, kv_src, compute_dtype), hkv)
    if cross_kv is None:
        pos = positions if positions is not None else jnp.arange(s)
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    if hq != hkv:
        # uniform true-group GQA mapping (single-device and TP agree)
        k, v = _gather_kv_for_local_q(k, v, cfg, hq, ctx)
    window = None if is_global else cfg.window
    chunk = None if is_global else cfg.chunk
    o = blockwise_attention(q, k, v, causal=causal and cross_kv is None,
                            window=window, chunk=chunk, block_q=block_q,
                            block_k=block_k, causal_skip=causal_skip)
    y = dense(p["wo"], _merge_heads(o), compute_dtype)
    return ctx.psum(y) if _needs_psum(p, cfg) else y


def attn_decode(p: dict, x1: jax.Array, cfg: AttnConfig, cache: dict, *,
                is_global: bool, ctx, pos: jax.Array,
                compute_dtype=jnp.bfloat16,
                cache_len_global: int | None = None) -> tuple:
    """One-token decode. ``cache``: {"k","v"}: (B,Hkv,C_local,D).

    When ``C_local < cache_len_global`` the cache is *sequence-sharded* over
    the model axis (context-parallel decode — the only way a 32k x 128 KV
    cache fits when kv heads replicate): each rank scores its slot range and
    the softmax is combined with pmax/psum partial statistics.
    """
    hq = p["wq"]["w"].shape[1] // cfg.head_dim
    hkv = p["wk"]["w"].shape[1] // cfg.head_dim
    q = _split_heads(dense(p["wq"], x1, compute_dtype), hq)        # (B,Hq,1,D)
    k1 = _split_heads(dense(p["wk"], x1, compute_dtype), hkv)
    v1 = _split_heads(dense(p["wv"], x1, compute_dtype), hkv)
    posv = jnp.asarray(pos)
    pos1 = posv.reshape(1)
    q = apply_rope(q, pos1, cfg.rope_theta)
    k1 = apply_rope(k1, pos1, cfg.rope_theta)
    c_local = cache["k"].shape[2]
    c_total = cache_len_global or c_local
    seq_sharded = c_local < c_total

    if not seq_sharded:
        slot = jnp.mod(posv, c_local)
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k1.astype(cache["k"].dtype), slot, axis=2)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v1.astype(cache["v"].dtype), slot, axis=2)
        kc, vc = k_cache, v_cache
        if hq != hkv:
            kc, vc = _gather_kv_for_local_q(kc, vc, cfg, hq, ctx)
        window = None if is_global else cfg.window
        chunk = None if is_global else cfg.chunk
        o = decode_attention(q, kc, vc, posv, window=window, chunk=chunk,
                             rolling=True)
    else:
        r = ctx.model_index()
        slot_g = jnp.mod(posv, c_total)
        ls = slot_g - r * c_local
        owner = (ls >= 0) & (ls < c_local)
        lsc = jnp.clip(ls, 0, c_local - 1)
        # masked single-slot write: only the owning rank's value changes
        def wr(buf, new):
            old = jax.lax.dynamic_slice_in_dim(buf, lsc, 1, axis=2)
            val = jnp.where(owner, new.astype(buf.dtype), old)
            return jax.lax.dynamic_update_slice_in_dim(buf, val, lsc, axis=2)
        k_cache = wr(cache["k"], k1)
        v_cache = wr(cache["v"], v1)
        kc, vc = k_cache, v_cache
        if hq != hkv:
            kc, vc = _gather_kv_for_local_q(kc, vc, cfg, hq, ctx)
        # local partial attention over this rank's slots
        slot_l = r * c_local + jnp.arange(c_local)            # global slots
        delta = jnp.mod(posv - slot_l, c_total)
        k_pos = posv - delta
        valid = (k_pos <= posv) & (k_pos >= 0)
        window = None if is_global else cfg.window
        chunk = None if is_global else cfg.chunk
        if window is not None:
            valid &= k_pos > posv - window
        if chunk is not None:
            valid &= (k_pos // chunk) == (posv // chunk)
        d = cfg.head_dim
        s = jnp.einsum("bhqd,bhkd->bhqk",
                       q.astype(jnp.float32) / math.sqrt(d),
                       kc.astype(jnp.float32))
        s = jnp.where(valid[None, None, None, :], s, NEG_INF)
        m = ctx.pmax(jnp.max(s, axis=-1, keepdims=True))
        e = jnp.exp(s - m)
        num = ctx.psum(jnp.einsum("bhqk,bhkd->bhqd", e,
                                  vc.astype(jnp.float32)))
        den = ctx.psum(jnp.sum(e, axis=-1, keepdims=True))
        o = (num / jnp.maximum(den, 1e-30)).astype(q.dtype)

    y = dense(p["wo"], _merge_heads(o), compute_dtype)
    y = ctx.psum(y) if _needs_psum(p, cfg) else y
    return y, {"k": k_cache, "v": v_cache}


def init_cache(cfg: AttnConfig, batch: int, seq_len: int, *, is_global: bool,
               dtype=jnp.bfloat16) -> dict:
    """Cache length: full seq for global layers, window/chunk for local."""
    c = seq_len
    if not is_global:
        if cfg.window is not None:
            c = min(c, cfg.window)
        elif cfg.chunk is not None:
            c = min(c, cfg.chunk)
    hkv = cfg.num_kv_heads
    return {"k": jnp.zeros((batch, hkv, c, cfg.head_dim), dtype),
            "v": jnp.zeros((batch, hkv, c, cfg.head_dim), dtype)}

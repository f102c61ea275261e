"""Shared building blocks: inits, norms, linears, RoPE, activations.

Parameters are plain nested dicts of ``jnp`` arrays (framework-neutral
pytrees); every constructor returns ``(params, apply_fn)``-style helpers as
free functions so the transformer assembly stays explicit and auditable.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def trunc_normal(key, shape, std: float, dtype=jnp.float32):
    return jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) \
        .astype(dtype) * jnp.asarray(std, dtype)


def dense_init(key, d_in: int, d_out: int, *, dtype=jnp.float32,
               bias: bool = False, std: float | None = None) -> dict:
    std = std if std is not None else 1.0 / math.sqrt(d_in)
    p = {"w": trunc_normal(key, (d_in, d_out), std, dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(p: dict, x: jax.Array, compute_dtype) -> jax.Array:
    w = p["w"].astype(compute_dtype)
    y = x.astype(compute_dtype) @ w
    if "b" in p:
        y = y + p["b"].astype(compute_dtype)
    return y


def rmsnorm_init(d: int, dtype=jnp.float32) -> dict:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p: dict, x: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(dt)


def activation(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}[name]


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, H, S, D); positions: (B, S) or (S,)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                          # (D/2,)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[:, None, :, None].astype(jnp.float32) * freqs  # (B,1,S,D/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    rx1 = x1 * cos - x2 * sin
    rx2 = x2 * cos + x1 * sin
    return jnp.concatenate([rx1, rx2], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def glu_mlp_init(key, d: int, f: int, *, dtype=jnp.float32) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {"w_gate": dense_init(k1, d, f, dtype=dtype),
            "w_up": dense_init(k2, d, f, dtype=dtype),
            "w_down": dense_init(k3, f, d, dtype=dtype)}


def glu_mlp(p: dict, x: jax.Array, act: str, compute_dtype, ctx=None,
            global_ff: int | None = None) -> jax.Array:
    """Col-parallel gate/up, row-parallel down; psum iff ff dim is a local
    TP shard (detected from the weight shape vs the config's global ff)."""
    g = dense(p["w_gate"], x, compute_dtype)
    u = dense(p["w_up"], x, compute_dtype)
    y = dense(p["w_down"], activation(act)(g) * u, compute_dtype)
    if ctx is not None and global_ff is not None \
            and p["w_down"]["w"].shape[0] < global_ff:
        y = ctx.psum(y)
    return y


# ---------------------------------------------------------------------------
# embeddings / unembedding
# ---------------------------------------------------------------------------


def embed_init(key, vocab: int, d: int, *, dtype=jnp.float32) -> dict:
    # 0.02 (GPT-2/llama-style): keeps tied-unembedding logits O(1) at init
    return {"table": trunc_normal(key, (vocab, d), 0.02, dtype)}


def embed(p: dict, tokens: jax.Array, compute_dtype, ctx, global_vocab: int) -> jax.Array:
    """Vocab-parallel embedding: local table shard, masked take, psum.

    The rows are gathered at the table's stored dtype and cast after: a
    cast of the table before the take would convert the whole table on
    every call to read a few rows of it."""
    table = p["table"]
    v_local = table.shape[0]
    if v_local == global_vocab:
        return jnp.take(table, tokens, axis=0).astype(compute_dtype)
    off = ctx.model_index() * v_local
    idx = tokens - off
    valid = (idx >= 0) & (idx < v_local)
    out = jnp.take(table, jnp.clip(idx, 0, v_local - 1),
                   axis=0).astype(compute_dtype)
    out = jnp.where(valid[..., None], out, 0)
    return ctx.psum(out)


def unembed(p: dict, x: jax.Array, compute_dtype) -> jax.Array:
    """Tied unembedding: col-parallel — local logits over the vocab shard."""
    return x.astype(compute_dtype) @ p["table"].astype(compute_dtype).T


def softmax_xent(logits: jax.Array, labels: jax.Array, mask: jax.Array | None,
                 ctx=None, global_vocab: int | None = None):
    """Token-mean cross entropy in fp32.  ``logits`` may be the *local* vocab
    shard (B, S, V_local) — pass ``ctx`` + ``global_vocab`` for the
    vocab-parallel reduction (max / logsumexp / gold-pick psums)."""
    lf = logits.astype(jnp.float32)
    v_local = lf.shape[-1]
    sharded = (ctx is not None and global_vocab is not None
               and v_local != global_vocab)
    if not sharded:
        logz = jax.nn.logsumexp(lf, axis=-1)
        gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    else:
        # the shared max is a numerical-stability shift only; stop_gradient
        # keeps it out of autodiff (pmax has no VJP, and logsumexp is
        # invariant to the shift anyway)
        m_loc = jax.lax.stop_gradient(jnp.max(lf, axis=-1))
        m = ctx.pmax(m_loc)
        se = ctx.psum(jnp.sum(jnp.exp(lf - m[..., None]), axis=-1))
        logz = jnp.log(se) + m
        off = ctx.model_index() * v_local
        idx = labels - off
        valid = (idx >= 0) & (idx < v_local)
        g = jnp.take_along_axis(lf, jnp.clip(idx, 0, v_local - 1)[..., None],
                                axis=-1)[..., 0]
        gold = ctx.psum(jnp.where(valid, g, 0.0))
    nll = logz - gold
    if mask is None:
        return jnp.mean(nll)
    m_ = mask.astype(jnp.float32)
    return jnp.sum(nll * m_) / jnp.maximum(jnp.sum(m_), 1.0)

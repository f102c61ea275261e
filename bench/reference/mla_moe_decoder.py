"""Plain reference of a decoder with multi-head latent attention and expert
layers (DeepSeek-V3's layers, as Moonlight-16B-A3B publishes them), in
float32.

Pre-norm blocks.  Attention is MLA with no query LoRA, computed in the
naive form: per token ``h``

    [q_nope_h | q_pe_h] = (h W_q)_h
    [c | k_pe]          = h W_kva,   c = RMSNorm(c)
    [k_nope_h | v_h]    = (c W_kvb)_h
    score_h(s, t) = (q_nope_h(s) . k_nope_h(t) + q_pe_h(s) . k_pe(t)) / sqrt(dn + dr)

with causal softmax, ``o = concat_h(softmax(score_h) v_h) W_o``: every
head's keys and values expanded from the latent rows (the program scores
the latent rows themselves; this is the independent formulation).  RoPE
rotates ``q_pe`` and ``k_pe`` (one for all heads) as the published
modelling code does, pair ``(x[2i], x[2i+1])`` by ``pos * theta^(-2i/dr)``;
it is applied here to the interleaved pairs in place (the published code
regroups them as evens and odds first, which permutes the query and the
key alike and leaves every score the same).

The leading ``dense_layers`` layers end in a SwiGLU MLP.  The others end
in an expert layer, this chip's share of it: the router scores all
``experts`` (``s = sigmoid(h W_r)``), selects the ``top_k`` largest
``s + score_bias`` (the bias only selects), weighs the chosen by
``s / sum(s) * scaling`` over all ``top_k`` of them, and adds the
weighted outputs of the chosen experts among the ``held`` it holds (from
``first``), plus the shared experts.  What the experts held elsewhere
would add is left out, as in the program.

Nothing of the program is imported.  Matrix products run at
``Precision.HIGHEST``.  ``prec="fp8"`` is the control: every matrix
product's operands are rounded to float8 e4m3 with a per-tensor scale.
Everything runs one layer at a time.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
NEG = -1e30


def _round8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / float(
        jnp.finfo(jnp.float8_e4m3fn).max)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(a, b, prec: str):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if prec == "fp8":
        a, b = _round8(a), _round8(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def rmsnorm(x, scale, eps):
    return (x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * scale.astype(jnp.float32))


def rope_interleaved(x, pos, theta):
    """x: (S, H, D): rotate each pair (x[2i], x[2i+1]) by
    pos * theta^(-2i/D), in place."""
    d = x.shape[-1]
    freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * freq            # (S, D/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]  # (S, 1, D/2)
    xe, xo = x[..., 0::2], x[..., 1::2]
    return jnp.stack([xe * cos - xo * sin, xo * cos + xe * sin],
                     axis=-1).reshape(x.shape)


def _attend(q, k, v, q0: int, sc: float):
    """Causal attention of queries at positions ``q0 + i``; q, k: (S, H, D),
    v: (S, H, Dv)."""
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) * sc
    qi = q0 + jnp.arange(q.shape[0])[:, None]
    ki = jnp.arange(k.shape[0])[None, :]
    p = jax.nn.softmax(jnp.where(ki <= qi, s, NEG), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)


def attention(a, x, m: dict, prec: str, q_block: int = 512):
    """MLA, naive form, over one sequence x: (S, d)."""
    s = x.shape[0]
    hh, dn, dr, dv, r = m["heads"], m["nope"], m["rope"], m["v"], m["lora"]
    pos = jnp.arange(s)
    q = mm(x, a["q_proj"]["w"], prec).reshape(s, hh, dn + dr)
    kv = mm(x, a["kv_a_proj"]["w"], prec)
    c = rmsnorm(kv[:, :r], a["kv_a_norm"]["scale"], m["eps"])
    k_pe = rope_interleaved(kv[:, None, r:], pos, m["theta"])   # (S, 1, dr)
    kvb = mm(c, a["kv_b_proj"]["w"], prec).reshape(s, hh, dn + dv)
    q = jnp.concatenate([q[..., :dn],
                         rope_interleaved(q[..., dn:], pos, m["theta"])],
                        axis=-1)
    k = jnp.concatenate([kvb[..., :dn],
                         jnp.broadcast_to(k_pe, (s, hh, dr))], axis=-1)
    v = kvb[..., dn:]
    if prec == "fp8":
        q, k, v = _round8(q), _round8(k), _round8(v)
    sc = 1.0 / math.sqrt(dn + dr)
    outs = []
    for q0 in range(0, s, q_block):
        q1 = min(q0 + q_block, s)
        f = jax.checkpoint(functools.partial(_attend, q0=q0, sc=sc))
        outs.append(f(q[q0:q1], k[:q1], v[:q1]))
    o = jnp.concatenate(outs, axis=0).reshape(s, hh * dv)
    return mm(o, a["o_proj"]["w"], prec)


def glu(p, h, prec):
    return mm(jax.nn.silu(mm(h, p["w_gate"]["w"], prec))
              * mm(h, p["w_up"]["w"], prec), p["w_down"]["w"], prec)


def experts(p, h, m: dict, prec: str):
    """This chip's share of the expert layer over tokens h: (S, d)."""
    s = jax.nn.sigmoid(mm(h, p["router"]["w"], prec))             # (S, E)
    _, ids = lax.top_k(s + p["score_bias"].astype(jnp.float32), m["top_k"])
    w = jnp.take_along_axis(s, ids, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True) * m["scaling"]      # (S, k)
    y = glu(p["shared"], h, prec)
    for j in range(m["held"]):
        gate = jnp.sum(jnp.where(ids == m["first"] + j, w, 0.0), axis=-1)
        hj = (jax.nn.silu(mm(h, p["w_gate"][j], prec))
              * mm(h, p["w_up"][j], prec))
        y = y + gate[:, None] * mm(hj, p["w_down"][j], prec)
    return y


def block(lp, x, m: dict, prec: str):
    """One pre-norm block; x: (S, d) float32."""
    x = x + attention(lp["mla"], rmsnorm(x, lp["ln1"]["scale"], m["eps"]),
                      m, prec)
    h = rmsnorm(x, lp["ln2"]["scale"], m["eps"])
    if "mlp" in lp:
        return x + glu(lp["mlp"], h, prec)
    return x + experts(lp["moe"], h, m, prec)


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("m", "prec"))
def _block_fwd(lp, x, *, m, prec):
    return block(lp, x, dict(m), prec)


@functools.partial(jax.jit, static_argnames=("m", "prec"))
def _head_fwd(params, x, *, m, prec):
    m = dict(m)
    h = rmsnorm(x, params["final_norm"]["scale"], m["eps"])
    w = (params["embed"]["table"].T if m["tied"] else params["lm_head"]["w"])
    return mm(h, w[:, :m["vocab"]], prec)


def stream_logits(params, tokens, m: dict, prec: str = "float32"):
    """Logits (T, vocab) at every position of ``tokens`` (T,)."""
    fm = tuple(sorted(m.items()))
    x = _embed(params["embed"]["table"], jnp.asarray(tokens))
    for lp in params["blocks"]:
        x = _block_fwd(lp, x, m=fm, prec=prec)
    with jax.default_matmul_precision("highest"):
        return _head_fwd({k: v for k, v in params.items() if k != "blocks"},
                         x, m=fm, prec=prec)

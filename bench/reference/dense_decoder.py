"""Plain reference of a dense decoder-only transformer, in float32.

Pre-norm blocks: RMSNorm, rotary position embeddings (half-split pairs),
causal grouped-query attention, SwiGLU MLP; a final RMSNorm and an output
projection (the embedding table when tied).  This is the published
architecture of both MiniCPM-2B and Phi-3-medium, minus MiniCPM's muP
scalars (see its configuration's ``assumed``).  It computes the published
model: its query heads (head ``h`` reads key head ``h // group``) and its
softmax over the published vocabulary.  The weights come in the program's
padded layout; the reference reads only their published part, so the
padded query columns, output rows and vocabulary rows take no part in the
result and their gradient is zero.

Nothing of the program is imported.  Matrix products run at
``Precision.HIGHEST``.  ``prec="fp8"`` is the control: every matrix
product's operands are rounded to float8 e4m3, and their gradients to
e5m2, each with a per-tensor scale.

Everything runs one layer at a time, so the reference fits beside nothing
but its own weights, and training keeps only the layer inputs between the
forward pass and the backward pass.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
NEG = -1e30


def _scaled(x, dtype):
    """``x`` rounded to an fp8 ``dtype`` under a per-tensor scale."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / float(jnp.finfo(dtype).max)
    return (x / s).astype(dtype).astype(jnp.float32) * s


@jax.custom_vjp
def _round8(x):
    """Operands in e4m3, and their cotangents in e5m2, each with its own
    per-tensor scale (the usual fp8 training recipe)."""
    return _scaled(x, jnp.float8_e4m3fn)


_round8.defvjp(lambda x: (_scaled(x, jnp.float8_e4m3fn), None),
               lambda _, g: (_scaled(g, jnp.float8_e5m2),))


def mm(a, b, prec: str):
    if prec == "fp8":
        a, b = _round8(a), _round8(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def rmsnorm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """x: (B, S, H, D); rotate the halves (x1, x2) by pos * theta^(-2i/D)."""
    d = x.shape[-1]
    freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * freq            # (S, D/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]  # (S, 1, D/2)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attend(q, k, v, q0: int):
    """Causal attention of queries at positions ``q0 + i`` over all keys.
    q: (B, Sq, H, D), k/v: (B, Sk, H, D)."""
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) / math.sqrt(d)
    qi = q0 + jnp.arange(q.shape[1])[:, None]
    ki = jnp.arange(k.shape[1])[None, :]
    s = jnp.where(ki <= qi, s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)


def block(lp, x, m: dict, prec: str, q_block: int = 512):
    """One pre-norm decoder block; x: (B, S, d) float32."""
    b, s, _ = x.shape
    hq, hkv, hd = m["heads"], m["kv_heads"], m["hd"]
    pos = jnp.arange(s)
    h = rmsnorm(x, lp["ln1"]["scale"], m["eps"])
    a = lp["attn"]
    wq, wo = a["wq"]["w"][:, :hq * hd], a["wo"]["w"][:hq * hd]
    q = rope(mm(h, wq, prec).reshape(b, s, hq, hd), pos, m["theta"])
    k = rope(mm(h, a["wk"]["w"], prec).reshape(b, s, hkv, hd), pos, m["theta"])
    v = mm(h, a["wv"]["w"], prec).reshape(b, s, hkv, hd)
    idx = jnp.arange(hq) // (hq // hkv)
    k, v = k[:, :, idx], v[:, :, idx]
    if prec == "fp8":
        q, k, v = _round8(q), _round8(k), _round8(v)
    outs = []
    for q0 in range(0, s, q_block):
        q1 = min(q0 + q_block, s)
        f = jax.checkpoint(functools.partial(_attend, q0=q0))
        outs.append(f(q[:, q0:q1], k[:, :q1], v[:, :q1]))
    o = jnp.concatenate(outs, axis=1).reshape(b, s, hq * hd)
    x = x + mm(o, wo, prec)
    h = rmsnorm(x, lp["ln2"]["scale"], m["eps"])
    p = lp["mlp"]
    y = jax.nn.silu(mm(h, p["w_gate"]["w"], prec)) * mm(h, p["w_up"]["w"], prec)
    return x + mm(y, p["w_down"]["w"], prec)


def head_weight(params, m: dict):
    """The output projection over the published vocabulary."""
    if m["tied"]:
        return params["embed"]["table"][:m["vocab"]].T
    return params["lm_head"]["w"][:, :m["vocab"]]


def logits(params, x, m: dict, prec: str):
    h = rmsnorm(x, params["final_norm"]["scale"], m["eps"])
    return mm(h, head_weight(params, m), prec)


# ---------------------------------------------------------------------------
# serving: logits of every position of one token stream
# ---------------------------------------------------------------------------


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("m", "prec"))
def _block_fwd(lp, x, *, m, prec):
    return block(lp, x, dict(m), prec)


@functools.partial(jax.jit, static_argnames=("m", "prec"))
def _head_fwd(params, x, *, m, prec):
    return logits(params, x, dict(m), prec)


def _frozen(m: dict):
    return tuple(sorted(m.items()))


def stream_logits(params, tokens, m: dict, prec: str = "float32"):
    """Logits (T, vocab) at every position of ``tokens`` (T,), over the
    published vocabulary."""
    fm = _frozen(m)
    x = _embed(params["embed"]["table"], jnp.asarray(tokens)[None])
    for lp in params["blocks"]:
        x = _block_fwd(lp, x, m=fm, prec=prec)
    with jax.default_matmul_precision("highest"):
        return _head_fwd({k: v for k, v in params.items() if k != "blocks"},
                         x, m=fm, prec=prec)[0]


# ---------------------------------------------------------------------------
# training: AdamW steps, one layer at a time
# ---------------------------------------------------------------------------


def _loss_chunked(head_params, x, labels, m: dict, prec: str, chunk: int = 512):
    """Mean token cross entropy over the published vocabulary, a ``chunk`` of
    positions at a time (a scan, so the head's gradient has one buffer)."""
    b, s, d = x.shape
    chunk = min(chunk, s)
    n = s // chunk
    xs = x.reshape(b, n, chunk, d).swapaxes(0, 1)
    ls = labels.reshape(b, n, chunk).swapaxes(0, 1)

    @jax.checkpoint
    def part(total, xl):
        xc, lc = xl
        lg = logits(head_params, xc, m, prec)
        lz = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, lc[..., None], axis=-1)[..., 0]
        return total + jnp.sum(lz - gold), None

    total, _ = lax.scan(part, jnp.zeros((), jnp.float32), (xs, ls))
    return total / (b * s)


@functools.partial(jax.jit, static_argnames=("m", "prec"))
def _head_grad(head_params, x, labels, *, m, prec):
    return jax.value_and_grad(
        lambda hp, xx: _loss_chunked(hp, xx, labels, dict(m), prec),
        argnums=(0, 1))(head_params, x)


@functools.partial(jax.jit, static_argnames=("m", "prec"))
def _block_vjp(lp, x, g, *, m, prec):
    _, pull = jax.vjp(lambda p, xx: block(p, xx, dict(m), prec), lp, x)
    return pull(g)


@jax.jit
def _sq(tree):
    return [jnp.sum(jnp.square(t)) for t in jax.tree.leaves(tree)]


@jax.jit
def _embed_grad(table_grad, tokens, dx):
    return table_grad.at[tokens].add(dx)


@functools.partial(jax.jit, static_argnames=("opt",), donate_argnums=(0, 2, 3))
def _adamw(p, g, mu, nu, c, lr, t, *, opt):
    o = dict(opt)

    def one(p_, g_, m_, v_):
        g_ = g_ * c
        m_ = o["b1"] * m_ + (1 - o["b1"]) * g_
        v_ = o["b2"] * v_ + (1 - o["b2"]) * g_ * g_
        mh = m_ / (1 - o["b1"] ** t)
        vh = v_ / (1 - o["b2"] ** t)
        return (p_ * (1 - lr * o["weight_decay"])
                - lr * (mh / (jnp.sqrt(vh) + o["eps"])), m_, v_)

    out = jax.tree.map(one, p, g, mu, nu)
    pick = lambda i: jax.tree.map(lambda _, r: r[i], p, out)  # noqa: E731
    return pick(0), pick(1), pick(2)


def lr_at(opt: dict, step: int) -> float:
    """Linear warmup, then warmup-stable-decay with a 1-sqrt decay over the
    last 20% of the steps (MiniCPM's recipe)."""
    if opt["schedule"] != "wsd":
        raise ValueError(f"unknown schedule {opt['schedule']!r}")
    w = max(opt["warmup"], 1)
    warm = min(step / w, 1.0)
    total = opt["total_steps"]
    stable_end = w + int((total - w) * 0.8)
    t = min(max((step - stable_end) / max(total - stable_end, 1), 0.0), 1.0)
    decay = 1.0 - 0.9 * math.sqrt(t)
    return opt["base_lr"] * warm * (1.0 if step < stable_end else decay)


class TrainReference:
    """AdamW training of the reference from ``params`` (a tree in the
    weights' layout; consumed).  ``sharding`` puts each batch's rows on
    the devices of a one-axis mesh, the parameters replicated."""

    def __init__(self, params, m: dict, opt: dict, prec: str = "float32",
                 rows=None, replicated=None):
        self.m, self.fm = m, _frozen(m)
        self.opt = tuple(sorted(opt.items()))
        self.opt_d = opt
        self.prec = prec
        self.rows, self.replicated = rows, replicated
        self.p = params
        zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t),
                        out_shardings=replicated)
        self.mu, self.nu = zeros(params), zeros(params)
        self.step_no = 0

    def _head(self):
        keys = ["final_norm", "embed"] + ([] if self.m["tied"] else ["lm_head"])
        return {k: self.p[k] for k in keys}

    def _backward(self, xs, tokens, g_top, update):
        """Gradient of every leaf, layer by layer from the top; with
        ``update=(c, lr, t)`` each layer is updated as soon as its
        gradient is known (its input gradient is already taken)."""
        sq = {}
        g = g_top
        for i in reversed(range(len(self.p["blocks"]))):
            dlp, g = _block_vjp(self.p["blocks"][i], xs[i], g, m=self.fm,
                                prec=self.prec)
            if update is None:
                sq[("blocks", i)] = _sq(dlp)
            else:
                self._apply(("blocks", i), dlp, *update)
        return g, sq

    def _apply(self, key, grad, c, lr, t):
        mu, nu, p = self.mu, self.nu, self.p
        for k in key[:-1]:
            mu, nu, p = mu[k], nu[k], p[k]
        last = key[-1]
        p[last], mu[last], nu[last] = _adamw(p[last], grad, mu[last], nu[last],
                                             c, lr, t, opt=self.opt)

    def step(self, tokens, labels) -> dict:
        """One AdamW step on ``(tokens, labels)`` (B, S).  Returns the loss
        and, per leaf, the clipped gradient's L2 norm (as the optimizer
        takes it)."""
        if self.rows is not None:
            tokens = jax.device_put(tokens, self.rows)
            labels = jax.device_put(labels, self.rows)
        x = _embed(self.p["embed"]["table"], tokens)
        xs = []
        for lp in self.p["blocks"]:
            xs.append(x)
            x = _block_fwd(lp, x, m=self.fm, prec=self.prec)
        loss, (g_head, g_top) = _head_grad(self._head(), x, labels, m=self.fm,
                                           prec=self.prec)
        del x
        g_bottom, sq = self._backward(xs, tokens, g_top, None)
        g_table = _embed_grad(g_head["embed"]["table"], tokens, g_bottom)
        del g_bottom
        sq_root = {"final_norm": _sq(g_head["final_norm"]),
                   "embed": _sq(g_table)}
        if not self.m["tied"]:
            sq_root["lm_head"] = _sq(g_head["lm_head"])
        total = sum(float(v) for vs in list(sq.values()) + list(sq_root.values())
                    for v in vs)
        gnorm = math.sqrt(total)
        c = min(1.0, self.opt_d["clip_norm"] / max(gnorm, 1e-12))
        norms = self._leaf_norms(sq, sq_root, c)
        lr = lr_at(self.opt_d, self.step_no)
        t = float(self.step_no + 1)
        upd = (jnp.float32(c), jnp.float32(lr), jnp.float32(t))
        g_bottom, _ = self._backward(xs, tokens, g_top, upd)
        del xs
        g_table = _embed_grad(g_head["embed"]["table"], tokens, g_bottom)
        self._apply(("embed", "table"), g_table, *upd)
        self._apply(("final_norm", "scale"), g_head["final_norm"]["scale"], *upd)
        if not self.m["tied"]:
            self._apply(("lm_head", "w"), g_head["lm_head"]["w"], *upd)
        self.step_no += 1
        return {"loss": float(loss), "grad_norm": gnorm, "leaf_grad_norms": norms}

    def _leaf_norms(self, sq, sq_root, c) -> list[float]:
        """Per leaf in the weights' leaf order (``jax.tree.leaves``)."""
        tree = {"blocks": [None] * len(self.p["blocks"])}
        for (_, i), v in sq.items():
            tree["blocks"][i] = jax.tree.unflatten(
                jax.tree.structure(self.p["blocks"][i]), v)
        tree["final_norm"] = {"scale": sq_root["final_norm"][0]}
        tree["embed"] = {"table": sq_root["embed"][0]}
        if "lm_head" in sq_root:
            tree["lm_head"] = {"w": sq_root["lm_head"][0]}
        return [c * math.sqrt(float(v)) for v in jax.tree.leaves(tree)]

"""Token-choice MoE with sort-based capacity dispatch (no fake-FLOP one-hot
einsums — the dry-run roofline only sees real expert matmuls plus data
movement, which is what a production dispatch does).

Per batch row: route tokens to ``top_k`` experts, sort the (token, expert)
pairs by expert, scatter into a (E, C, d) capacity buffer, run every expert
as one batched GLU matmul, gather back with gate weights.  Tokens beyond an
expert's capacity are dropped (standard capacity-factor semantics) and
reported via the ``drop_fraction`` metric; a shared expert (llama4) adds a
dense always-on path.

Parallelism modes (applied by ``sharding.rules``):
* ``ep`` — expert dim of the weights sharded over "model"; the capacity
  buffer is built per *batch shard* and exchanged through
  ``ctx.all_to_all`` (dispatch: batch-sharded in, expert-sharded out;
  combine: the inverse), so only ``1/R``-th of the buffer crosses the wire
  per hop instead of the old replicated psum's full copy.  When the batch
  does not divide the EP axis (e.g. decode micro-batches) the honest
  replicated-psum fallback below is used.
* ``tp`` — expert ffn dim sharded over "model" (for E smaller than the axis).

A layer told which experts it holds (``MoEConfig.experts_held``, from
``first_expert``) is one chip's share of an expert-parallel deployment:
it routes over all ``num_experts`` at the router's published width and
adds, for each token, the weighted outputs of the chosen experts it holds
(:func:`held_experts`), plus the shared experts every chip computes alike.
The weights still sum over all ``top_k`` chosen experts, held or not.
Every token passes through every held expert, weighted by a gate that is
zero where the expert was not chosen: exact, nothing dropped, and at
decode sizes (a token a slot) as memory-bound as a dispatch.  The chips
that hold the other experts, and the exchange with them, are not part of
it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.models.common import (activation, dense_init, glu_mlp,
                                 glu_mlp_init, trunc_normal)


def moe_init(key, cfg: MoEConfig, d_model: int, *, dtype=jnp.float32) -> dict:
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    e, f = held_count(cfg), cfg.expert_ff
    std = 1.0 / math.sqrt(d_model)
    p = {
        "router": dense_init(k1, d_model, cfg.num_experts, dtype=dtype),
        "w_gate": trunc_normal(k2, (e, d_model, f), std, dtype),
        "w_up": trunc_normal(k3, (e, d_model, f), std, dtype),
        "w_down": trunc_normal(k4, (e, f, d_model), 1.0 / math.sqrt(f), dtype),
    }
    if cfg.shared_expert_ff:
        p["shared"] = glu_mlp_init(k5, d_model, cfg.shared_expert_ff, dtype=dtype)
    if cfg.scoring == "sigmoid":
        p["score_bias"] = jnp.zeros((cfg.num_experts,), dtype)
    return p


def held_count(cfg: MoEConfig) -> int:
    """Experts whose weights the layer holds."""
    return cfg.num_experts if cfg.experts_held is None else cfg.experts_held


def route(p: dict, x: jax.Array, cfg: MoEConfig, compute_dtype
          ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """``(weights, expert_ids, probs)``: each token's ``top_k`` experts
    among all ``num_experts``, their fp32 gate weights, and every expert's
    routing probability (for the balance loss).

    ``softmax``: the top-k logits, their softmax renormalised (top-1: a
    sigmoid gate).  ``sigmoid`` (DeepSeek-V3 ``noaux_tc``, one group):
    ``s = sigmoid(x W_r)`` in fp32, the top-k of ``s + score_bias`` (the
    bias only selects), ``w = s / sum(s) * routed_scaling`` over the
    chosen."""
    if cfg.scoring == "sigmoid":
        logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                            p["router"]["w"].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        s = jax.nn.sigmoid(logits)
        _, ids = jax.lax.top_k(s + p["score_bias"].astype(jnp.float32),
                               cfg.top_k)
        w = jnp.take_along_axis(s, ids, axis=-1)
        w = w / jnp.sum(w, axis=-1, keepdims=True) * cfg.routed_scaling
        return w, ids, s / jnp.sum(s, axis=-1, keepdims=True)
    logits = jnp.einsum("...d,de->...e", x.astype(compute_dtype),
                        p["router"]["w"].astype(compute_dtype))
    logits = logits.astype(jnp.float32)
    gate_vals, ids = jax.lax.top_k(logits, cfg.top_k)
    if cfg.top_k == 1:
        # llama4-style: sigmoid gate (renorm-softmax of one logit is a
        # constant 1 and would starve the router of gradient)
        w = jax.nn.sigmoid(gate_vals)
    else:
        w = jax.nn.softmax(gate_vals, axis=-1)                     # mixtral renorm
    return w, ids, jax.nn.softmax(logits, axis=-1)


def held_experts(p: dict, x: jax.Array, gate: jax.Array, act: str,
                 compute_dtype) -> jax.Array:
    """sum_e gate[..., e] * expert_e(x) over the held experts, every token
    through every one of them.  x: (T, d), gate: (T, held) -> (T, d)."""
    xc = x.astype(compute_dtype)
    g = jnp.einsum("td,edf->tef", xc, p["w_gate"].astype(compute_dtype))
    u = jnp.einsum("td,edf->tef", xc, p["w_up"].astype(compute_dtype))
    h = activation(act)(g) * u * gate[..., None].astype(compute_dtype)
    return jnp.einsum("tef,efd->td", h, p["w_down"].astype(compute_dtype),
                      preferred_element_type=jnp.float32)


def held_moe(p: dict, x: jax.Array, cfg: MoEConfig, act: str, compute_dtype,
             valid: jax.Array | None = None, held=0
             ) -> tuple[jax.Array, jax.Array]:
    """The layer's held share at tokens x (T, d): route over all experts
    (scope ``moe_router``), :func:`held_experts` (``moe_experts``), plus
    the shared experts (``moe_shared``).  -> ``(y (T, d), held)``, the
    count ``held`` plus the (token, expert) choices on held experts of the
    tokens ``valid`` (T,) marks (None: all)."""
    with jax.named_scope("moe_router"):
        w, ids, _ = route(p, x, cfg, compute_dtype)
        # each held expert's weight where the token chose it, else zero
        experts = cfg.first_expert + jnp.arange(held_count(cfg))
        gate = jnp.sum(jnp.where(ids[..., None] == experts, w[..., None],
                                 0.0), axis=-2)                  # (T, held)
        mine = (ids >= cfg.first_expert) & (
            ids < cfg.first_expert + held_count(cfg))
        if valid is not None:
            mine = mine & valid[:, None]
        held = held + jnp.sum(mine, dtype=jnp.int32)
    with jax.named_scope("moe_experts"):
        y = held_experts(p, x, gate, act, compute_dtype)
    if "shared" in p:
        with jax.named_scope("moe_shared"):
            y = y + glu_mlp(p["shared"], x, act, compute_dtype)
    return y, held


def capacity(tokens_per_row: int, cfg: MoEConfig) -> int:
    c = int(math.ceil(tokens_per_row * cfg.top_k * cfg.capacity_factor
                      / cfg.num_experts))
    return max(8, int(math.ceil(c / 8) * 8))  # sublane-aligned


def load_balance_aux(gates_all: jax.Array, expert_ids: jax.Array,
                     num_experts: int, top_k: int) -> jax.Array:
    """Switch-style load-balancing loss, normalized so perfect balance is
    exactly 1.0 for *every* ``top_k``.

    ``me[e]`` is the mean router probability of expert ``e``; ``pe[e]`` is
    the mean number of top-k slots assigned to it divided by ``top_k``, so
    ``sum(pe) == 1`` regardless of k (the previous form collapsed top-k
    multiplicity through ``> 0`` and skipped the ``1/k``, making the
    balanced fixed point of ``E * sum(me * pe)`` drift to ``k`` — mixtral
    k=2 and llama4 k=1 losses were not comparable).
    """
    e = num_experts
    me = jnp.mean(gates_all, axis=(0, 1))                          # (E,)
    pe = jnp.mean(jax.nn.one_hot(expert_ids, e).sum(axis=2),
                  axis=(0, 1)) / top_k                             # (E,)
    return e * jnp.sum(me * pe)


def dropped_fraction(expert_ids: jax.Array, num_experts: int,
                     cap: int) -> jax.Array:
    """Fraction of (token, expert) assignments past capacity — the tokens
    :func:`moe_apply` silently zeroes.  Computed from the (replicated)
    routing decision alone, so it costs one one-hot sum and is identical on
    every rank."""
    b = expert_ids.shape[0]
    flat_ids = expert_ids.reshape(b, -1)                           # (B, S*k)
    t = flat_ids.shape[1]
    counts = jnp.sum(jax.nn.one_hot(flat_ids, num_experts,
                                    dtype=jnp.float32), axis=1)    # (B, E)
    over = jnp.maximum(counts - cap, 0.0)
    return jnp.sum(over) / (b * t)


def moe_apply(p: dict, x: jax.Array, cfg: MoEConfig, act: str, *, ctx,
              compute_dtype=jnp.bfloat16
              ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B, S, d) -> (y, aux_loss, drop_fraction).

    Activations are replicated over the TP axis (Megatron-style), so routing
    is computed identically on every model rank.
    * EP (batch divides the axis): each rank builds the capacity buffer for
      its *batch shard* only, ``ctx.all_to_all`` turns it expert-sharded
      (dispatch), local experts compute, the inverse all-to-all brings the
      outputs home, and an identity-backward all-gather replicates the
      combined result — no replicated buffer, no zero-pad psum.
    * EP (fallback): replicated buffer, slice own experts, zero-pad,
      psum — the honest replicated cost, also used by transport="psum".
    * TP: every rank runs all experts on its ffn shard; psum after w_down.
    * A held share (``cfg.experts_held``): :func:`held_moe`; no exchange,
      nothing dropped.
    """
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.top_k
    cap = capacity(s, cfg)
    xf = x.astype(compute_dtype)
    e_local = p["w_gate"].shape[0]
    f_local = p["w_gate"].shape[2]
    ep_sharded = e_local < held_count(cfg)
    tp_sharded = f_local < cfg.expert_ff

    gate_w, expert_ids, gates_all = route(p, xf, cfg, compute_dtype)
    aux = load_balance_aux(gates_all, expert_ids, e, k)
    if cfg.experts_held is not None:     # held_moe routes again
        y, _ = held_moe(p, xf.reshape(b * s, d), cfg, act, compute_dtype)
        return (y.reshape(b, s, d).astype(x.dtype), aux,
                jnp.zeros((), jnp.float32))
    drop_frac = dropped_fraction(expert_ids, e, cap)

    # ---- sort-based dispatch, vmapped over batch rows ----
    flat_ids = expert_ids.reshape(b, s * k)                        # (B, T)
    flat_gate = gate_w.reshape(b, s * k)
    tok_of = jnp.tile(jnp.arange(s)[:, None], (1, k)).reshape(s * k)
    sharded = ep_sharded or tp_sharded
    if sharded:
        # f-boundaries: dispatch input and gate values feed rank-partial
        # compute (local experts / local ffn shards); their cotangents are
        # per-rank partial sums.  The router-logits path stays replicated.
        xd = ctx.fan_out(xf)
        flat_gate = ctx.fan_out(flat_gate)
    else:
        xd = xf

    def dispatch_row(ids, xrow):
        order = jnp.argsort(ids, stable=True)                      # (T,)
        sorted_ids = ids[order]
        starts = jnp.searchsorted(sorted_ids, jnp.arange(e))       # (E,)
        pos_in_grp = jnp.arange(s * k) - starts[sorted_ids]
        keep = pos_in_grp < cap
        dest = jnp.where(keep, sorted_ids * cap + pos_in_grp, e * cap)
        buf = jnp.zeros((e * cap + 1, d), compute_dtype)
        buf = buf.at[dest].set(xrow[tok_of[order]].astype(compute_dtype))
        return buf[:-1].reshape(e, cap, d), order, dest, keep

    def combine_row(obuf, order_r, dest_r, keep_r, gate_r):
        flat = obuf.reshape(e * cap, d)
        vals = flat[jnp.minimum(dest_r, e * cap - 1)]              # (T, d)
        vals = vals * keep_r[:, None].astype(vals.dtype)
        g = gate_r[order_r][:, None].astype(vals.dtype)
        y = jnp.zeros((s, d), vals.dtype)
        return y.at[tok_of[order_r]].add(vals * g)

    wg = p["w_gate"].astype(compute_dtype)
    wu = p["w_up"].astype(compute_dtype)
    wd = p["w_down"].astype(compute_dtype)

    def glu(buf_c):
        h = activation(act)(jnp.einsum("becd,edf->becf", buf_c, wg)) * \
            jnp.einsum("becd,edf->becf", buf_c, wu)
        return jnp.einsum("becf,efd->becd", h, wd)

    r = ctx.model_size() if ep_sharded else 1
    if ep_sharded and r > 1 and b % r == 0:
        # ---- expert-parallel via all-to-all ----
        bs = b // r
        i0 = ctx.model_index() * bs
        xd_s = jax.lax.dynamic_slice_in_dim(xd, i0, bs, axis=0)
        ids_s = jax.lax.dynamic_slice_in_dim(flat_ids, i0, bs, axis=0)
        gate_s = jax.lax.dynamic_slice_in_dim(flat_gate, i0, bs, axis=0)
        buf_s, order_s, dest_s, keep_s = jax.vmap(dispatch_row)(ids_s, xd_s)
        # dispatch: (bs, E, C, d) batch-sharded -> (B, E_l, C, d) expert-sharded
        recv = ctx.all_to_all(buf_s, split_axis=1, concat_axis=0)
        out = glu(recv)                                      # (B, E_l, C, d)
        # combine: the inverse exchange brings expert outputs home
        back = ctx.all_to_all(out, split_axis=0, concat_axis=1)
        y_s = jax.vmap(combine_row)(back, order_s, dest_s, keep_s, gate_s)
        y = ctx.gather_replicated(y_s)                       # (B, S, d)
    else:
        buf, order, dest, keep = jax.vmap(dispatch_row)(flat_ids, xd)
        if ep_sharded:
            # replicated-psum fallback: slice this rank's expert rows out of
            # the (replicated) buffer, zero-pad back, psum merges subsets
            e0 = ctx.model_index() * e_local
            buf_c = jax.lax.dynamic_slice_in_dim(buf, e0, e_local, axis=1)
        else:
            buf_c = buf
        out_buf = glu(buf_c)                                 # (B, E_l, C, d)
        if ep_sharded:
            full = jnp.zeros((b, e, cap, d), out_buf.dtype)
            out_buf = jax.lax.dynamic_update_slice_in_dim(full, out_buf, e0,
                                                          axis=1)
        y = jax.vmap(combine_row)(out_buf, order, dest, keep, flat_gate)
        if ep_sharded or tp_sharded:
            y = ctx.psum(y)

    if "shared" in p:
        xs = ctx.fan_out(xf) if p["shared"]["w_down"]["w"].shape[0] < \
            cfg.shared_expert_ff else xf
        y = y + glu_mlp(p["shared"], xs, act, compute_dtype, ctx,
                        cfg.shared_expert_ff)
    return y.astype(x.dtype), aux, drop_frac

"""Split-KV decode attention statistics — Pallas TPU kernel.

Decode is the α-bound regime: one query token against a long KV history.
The kernel tiles the key positions (grid ``(batch, q_heads, k_blocks)``,
trailing dim sequential) and emits **unnormalised** partial statistics
``(acc, m, l)`` instead of the finished output, so callers can merge
shards — per-device KV pages, per-page splits — with a log-sum-exp
combine (:func:`repro.kernels.flash_decode.ref.combine`).  That combine is
what the paged engine turns into a single fused ``Communicator.all_reduce``
across the model axis.

GQA is folded into the index maps (q head ``h`` reads kv head
``h // group``), same as the prefill flash kernel.  A ``valid`` mask (not
causality) gates key positions: paged KV holds many sequences at different
lengths in one fixed-shape buffer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(q_ref, k_ref, v_ref, valid_ref, acc_o, m_o, l_o,
                   m_s, l_s, acc_s, *, scale: float):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    # Op-for-op the loop body of ref.decode_stats_blockwise — keep the two
    # implementations in lockstep; the lockstep test depends on it.
    q = q_ref[0, 0].astype(jnp.float32)              # (1, d)
    k = k_ref[0, 0].astype(jnp.float32)              # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)              # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    ok = valid_ref[0] != 0                           # (1, bk)
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_s[...]                                # (1, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_s[...] = alpha * l_s[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_s[...] = acc_s[...] * alpha + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)
    m_s[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        acc_o[0, 0] = acc_s[...]
        m_o[0, 0] = m_s[...]
        l_o[0, 0] = l_s[...]


def flash_decode_stats_fwd(q: jax.Array, k: jax.Array, v: jax.Array,
                           valid: jax.Array, *, block_k: int = 128,
                           interpret: bool = False
                           ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """q: (B, Hq, 1, D); k/v: (B, Hkv, L, D); valid: (B, L) int/bool.

    Returns fp32 ``(acc (B,Hq,1,D), m (B,Hq,1,1), l (B,Hq,1,1))`` — the
    partial softmax statistics of this KV shard.
    """
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    if sq != 1:
        raise ValueError(f"decode kernel takes a single query token, got S={sq}")
    if hq % hkv != 0:
        raise ValueError(f"Hq={hq} not a multiple of Hkv={hkv}")
    group = hq // hkv
    bk = min(block_k, sk)
    if sk % bk:
        raise ValueError(f"L={sk} must tile by block_k={bk}")
    scale = 1.0 / (d ** 0.5)
    grid = (b, hq, sk // bk)
    # (B, 1, L): the mask block's trailing dims (1, bk) then tile for any
    # batch size (a (1, bk) block of a (B, L) array does not, unless B == 1)
    valid = valid.astype(jnp.int32)[:, None, :]

    kernel = functools.partial(_decode_kernel, scale=scale)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, d), lambda b_, h, j: (b_, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, j, g=group: (b_, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, j, g=group: (b_, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk), lambda b_, h, j: (b_, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, d), lambda b_, h, j: (b_, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1), lambda b_, h, j: (b_, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1), lambda b_, h, j: (b_, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, 1, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hq, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, hq, 1, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),    # running max m
            pltpu.VMEM((1, 1), jnp.float32),    # running denom l
            pltpu.VMEM((1, d), jnp.float32),    # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, valid)

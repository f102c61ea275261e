"""Split-KV decode attention statistics — Pallas TPU kernels.

Decode is the α-bound regime: one query token against a long KV history.
Both kernels tile the key positions and emit **unnormalised** partial
statistics ``(acc, m, l)`` instead of the finished output, so callers can
merge shards — per-device KV pages, per-page splits — with a log-sum-exp
combine (:func:`repro.kernels.flash_decode.ref.combine`).  That combine is
what the paged engine turns into a single fused ``Communicator.all_reduce``
across the model axis.

:func:`paged_decode_stats_fwd` (the paged engine's) reads the KV page
arena in place: one grid step per slot walks the slot's live pages by
scalar-prefetched page ids, double-buffering each page's DMA of K and V
rows for every kv head, and each q head scores its own kv head's rows of
that tile; blocks past a slot's position are neither fetched nor scored.
:func:`mla_decode_stats_fwd` (kernel name ``mla_decode``) walks the same
way over pages of latent rows: multi-head latent attention in its
absorbed form, every head scoring one shared row a token.
:func:`flash_decode_stats_fwd` scores one dense KV shard (grid
``(batch, q_heads, k_blocks)``, GQA folded into the index maps as q head
``h`` reading kv head ``h // group``) under a ``valid`` mask.
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


def _pack(d: int) -> int:
    """K/V rows of ``d`` lanes held in one 128-lane row of the arena."""
    return 128 // d if d < 128 else 1


def check_paged_tiling(num_kv_heads: int, page_tokens: int, head_dim: int,
                       page_elems: int) -> None:
    """Raise unless :func:`paged_decode_stats_fwd` compiles for the chip
    over pages of ``page_elems`` elements: a page's K (and V) rows,
    ``num_kv_heads * page_tokens`` of ``head_dim``, fill whole 8-row tiles
    of 128-lane rows (``head_dim`` divides 128 or is a multiple of it; the
    DMA moves whole tiles), and the page holds K and V.  Interpret mode
    takes any shape."""
    d, rows = head_dim, num_kv_heads * page_tokens
    lanes = d * _pack(d)
    if (128 % d if d < 128 else d % 128) or (rows * d) % (8 * lanes) \
            or page_elems % lanes or page_elems < 2 * rows * d:
        raise ValueError(
            f"the paged kernel reads a page's K and V as num_kv_heads * "
            f"page_tokens = {rows} rows of head_dim={d} (in 8-row tiles of "
            f"128 lanes) from pages of {page_elems} elements; pick a "
            f"page_tokens that tiles")


def _walk_live_pages(walk, copies, score, *, bpr: int, pt: int):
    """The page walk both paged kernels share: grid step ``b`` (one slot)
    loops over the slot's ``nlive[b]`` live pages, each page's copies
    (``copies(slot, i, buf)``, DMA descriptors into buffer ``buf``)
    double-buffered: the slot's next page, or after its last the next live
    slot's first, is in flight while ``score(copies, buf, last)`` scores
    this one (``last``: the slot's last live offset in the page)."""
    page_ref, blk_ref, nlive_ref, off_ref, nxt_ref, lens_ref = walk
    del page_ref
    b = pl.program_id(0)
    n, off = nlive_ref[b], off_ref[b]

    @pl.when((off == 0) & (n > 0))         # the first live page of the call
    def _first():
        for c in copies(b, 0, 0):
            c.start()

    def body(i, carry):
        buf = (off + i) % 2

        @pl.when(i + 1 < n)                # prefetch this slot's next page
        def _next_block():
            for c in copies(b, i + 1, 1 - buf):
                c.start()

        @pl.when((i + 1 == n) & (nxt_ref[b] >= 0))   # or the next slot's first
        def _next_slot():
            for c in copies(nxt_ref[b], 0, 1 - buf):
                c.start()

        score(copies(b, i, buf), buf,
              lens_ref[b] - blk_ref[b * bpr + i] * pt)
        return carry

    jax.lax.fori_loop(0, n, body, 0)


def _paged_decode_kernel(page_ref, blk_ref, nlive_ref, off_ref, nxt_ref,
                         lens_ref, q_ref, pages_hbm, acc_o, m_o, l_o, kbuf,
                         vbuf, sem, m_s, l_s, acc_s, *, scale: float,
                         group: int, hkv: int, pt: int, bpr: int, pack: int):
    prow = kbuf.shape[1]                   # lane rows of one page's K (or V)

    def copies(slot, i, buf):
        page = page_ref[slot * bpr + i]
        return (pltpu.make_async_copy(pages_hbm.at[page, pl.ds(0, prow)],
                                      kbuf.at[buf], sem.at[0, buf]),
                pltpu.make_async_copy(pages_hbm.at[page, pl.ds(prow, prow)],
                                      vbuf.at[buf], sem.at[1, buf]))

    m_s[...] = jnp.full_like(m_s, NEG_INF)
    l_s[...] = jnp.zeros_like(l_s)
    acc_s[...] = jnp.zeros_like(acc_s)
    hq, lanes = acc_s.shape
    d = lanes // pack
    # q head h reads kv head clip(h // group, 0, hkv - 1): rows
    # [lo(h), lo(h) + pt) of the page's K and V
    h = jax.lax.broadcasted_iota(jnp.int32, (hq, 1), 0)
    lo = jnp.zeros_like(h)
    for g in range(1, hkv):
        lo = lo + jnp.where(h >= g * group, pt, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (hq, prow), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)

    def score(cps, buf, last):
        ck, cv = cps
        ck.wait()
        k = kbuf[buf].astype(jnp.float32)               # (prow, lanes)
        # a lane row holds ``pack`` consecutive K rows of d lanes: piece c
        # scores row pack * col + c against q placed in lanes [c*d, (c+1)*d)
        s = []
        for c in range(pack):
            sc = jax.lax.dot_general(
                q_ref[0, c].astype(jnp.float32), k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            t = pack * col + c - lo
            s.append(jnp.where((t >= 0) & (t < pt) & (t <= last), sc,
                               NEG_INF))
        m_prev = m_s[...]                                # (hq, 1)
        m_new = m_prev
        for sc in s:
            m_new = jnp.maximum(m_new, jnp.max(sc, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = [jnp.exp(sc - m_new) for sc in s]
        l_s[...] = alpha * l_s[...] + sum(
            jnp.sum(pc, axis=-1, keepdims=True) for pc in p)
        cv.wait()
        v = vbuf[buf].astype(jnp.float32)
        pv = [jax.lax.dot(pc, v, preferred_element_type=jnp.float32)
              for pc in p]
        if pack > 1:                       # piece c's values: lanes of c
            pv = [jnp.where((lane >= c * d) & (lane < (c + 1) * d), x, 0.0)
                  for c, x in enumerate(pv)]
        acc_s[...] = acc_s[...] * alpha + sum(pv)
        m_s[...] = m_new

    _walk_live_pages((page_ref, blk_ref, nlive_ref, off_ref, nxt_ref,
                      lens_ref), copies, score, bpr=bpr, pt=pt)
    acc_o[0] = acc_s[...]
    m_o[0] = m_s[...]
    l_o[0] = l_s[...]


def _live_walk(tab, slot_len, slot_valid, first_block, pt: int):
    """The scalar-prefetch operands of a paged kernel's walk over the live
    pages of ``tab`` (B, bpr): ``(page, blk, nlive, off, nxt, lens)``.

    Each slot's live blocks come first, in order (``page`` and ``blk``
    flattened, ``bpr`` a slot); the kernel reads ``nlive[b]`` of them, the
    DMA buffer of the i-th is ``(off[b] + i) % 2``, and after a slot's last
    it prefetches the first page of ``nxt[b]``, the next slot with a live
    page (-1: none).  A block is live when mapped, of a valid slot, and
    starting at or before the slot's position ``lens[b]``."""
    b, bpr = tab.shape
    tab = tab.astype(jnp.int32)
    lens = slot_len.astype(jnp.int32)
    blk = jnp.asarray(first_block, jnp.int32) + jnp.arange(bpr,
                                                           dtype=jnp.int32)
    live = (tab >= 0) & slot_valid[:, None] & (blk[None, :] * pt
                                               <= lens[:, None])
    order = jnp.argsort(~live, axis=1, stable=True)
    page = jnp.maximum(jnp.take_along_axis(tab, order, axis=1), 0)
    nlive = live.sum(axis=1, dtype=jnp.int32)
    off = jnp.cumsum(nlive, dtype=jnp.int32) - nlive
    slots = jnp.arange(b, dtype=jnp.int32)
    ahead = jax.lax.cummin(jnp.where(nlive > 0, slots, b), reverse=True)
    nxt = jnp.concatenate([ahead[1:], jnp.full((1,), b, jnp.int32)])
    nxt = jnp.where(nxt < b, nxt, -1)
    return (page.reshape(-1), blk[order].reshape(-1), nlive, off, nxt, lens)


def paged_decode_stats_fwd(q: jax.Array, pages: jax.Array, tab: jax.Array,
                           slot_len: jax.Array, slot_valid: jax.Array,
                           first_block, *, num_kv_heads: int,
                           page_tokens: int, group: int,
                           interpret: bool = False
                           ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Split-KV decode statistics read in place from a paged KV arena.

    q: (B, Hq, 1, D).  pages: (n_pages, page_rows, D), a page's K for every
    kv head in rows ``[0, Hkv·pt)`` and its V in the next ``Hkv·pt`` rows.
    tab: (B, bpr) int32 page ids of the blocks ``first_block + [0, bpr)``
    (``-1`` unmapped).  Position ``p`` of slot ``b`` counts when
    ``p <= slot_len[b]``, its block is mapped and ``slot_valid[b]``.  Query
    head ``h`` reads kv head ``clip(h // group, 0, Hkv - 1)``.

    One grid step per slot loops over the slot's live pages only; each
    page's K and V arrive by DMA (double-buffered, the next page — or the
    next live slot's first — in flight while this one is scored), and
    every q head scores its own kv head's rows of them.  Rows narrower
    than 128 lanes are read ``128 // D`` to a lane row, as the arena
    holds them.  Returns fp32 ``(acc (B,Hq,1,D), m (B,Hq,1,1),
    l (B,Hq,1,1))`` like :func:`flash_decode_stats_fwd`; a slot with no
    live position returns ``m = NEG_INF``, ``l = 0``, ``acc = 0``, which
    merges with weight 0.
    """
    b, hq, sq, d = q.shape
    hkv, pt = num_kv_heads, page_tokens
    n_pages, page_rows, _ = pages.shape
    bpr = tab.shape[1]
    pack = _pack(d)
    lanes, prow = d * pack, hkv * pt // pack
    if sq != 1:
        raise ValueError(f"decode kernel takes a single query token, got S={sq}")
    if (hkv * pt) % pack or (page_rows * d) % lanes \
            or page_rows < 2 * hkv * pt:
        raise ValueError(f"pages {pages.shape} do not hold K and V of "
                         f"{hkv * pt} rows in rows of {lanes} lanes")
    walk = _live_walk(tab, slot_len, slot_valid, first_block, pt)
    # q in lanes [c*d, (c+1)*d) of piece c, zeros elsewhere
    qp = (q[:, None, :, 0, None, :]
          * jnp.eye(pack, dtype=q.dtype)[None, :, None, :, None])

    kernel = functools.partial(_paged_decode_kernel, scale=1.0 / (d ** 0.5),
                               group=group, hkv=hkv, pt=pt, bpr=bpr,
                               pack=pack)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, pack, hq, lanes), lambda b_, *_: (b_, 0, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, hq, lanes), lambda b_, *_: (b_, 0, 0)),
            pl.BlockSpec((1, hq, 1), lambda b_, *_: (b_, 0, 0)),
            pl.BlockSpec((1, hq, 1), lambda b_, *_: (b_, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, prow, lanes), pages.dtype),   # K, double-buffered
            pltpu.VMEM((2, prow, lanes), pages.dtype),   # V
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((hq, 1), jnp.float32),            # running max m
            pltpu.VMEM((hq, 1), jnp.float32),            # running denom l
            pltpu.VMEM((hq, lanes), jnp.float32),        # output accumulator
        ],
    )
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, lanes), jnp.float32),
            jax.ShapeDtypeStruct((b, hq, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, hq, 1), jnp.float32),
        ],
        # the slots run in order: each prefetches the next one's first page
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*walk, qp.reshape(b, pack, hq, lanes),
      pages.reshape(n_pages, page_rows * d // lanes, lanes))
    acc = acc.reshape(b, hq, pack, d).sum(axis=2)
    return (acc.reshape(b, hq, 1, d), m.reshape(b, hq, 1, 1),
            l.reshape(b, hq, 1, 1))


def check_latent_tiling(page_tokens: int, lane: int, latent_dim: int,
                        rope_pack: int, page_rows: int) -> None:
    """Raise unless :func:`mla_decode_stats_fwd` compiles for the chip: a
    page's latent rows (``latent_dim / lane`` blocks of ``page_tokens``
    rows) and its packed RoPE keys (``page_tokens / rope_pack`` rows) fill
    whole 16-row bf16 tiles of 128 lanes.  Interpret mode takes any
    shape."""
    nc = latent_dim // lane
    if lane != 128 or (page_tokens // rope_pack) % 16 \
            or page_rows < nc * page_tokens + page_tokens // rope_pack:
        raise ValueError(
            f"the latent kernel reads a page as {nc} x {page_tokens} latent "
            f"rows and {page_tokens} // {rope_pack} RoPE-key rows of {lane} "
            f"lanes, in 16-row tiles of 128 lanes; pick a page_tokens that "
            f"tiles")


def _mla_decode_kernel(page_ref, blk_ref, nlive_ref, off_ref, nxt_ref,
                       lens_ref, qc_ref, qpe_ref, pages_hbm, acc_o, m_o, l_o,
                       cbuf, pbuf, sem, m_s, l_s, acc_s, *, scale: float,
                       pt: int, bpr: int, rope: int):
    nc, hq, lane = acc_s.shape
    rows = pbuf.shape[1]                   # packed RoPE-key rows of a page

    def copies(slot, i, buf):
        page = page_ref[slot * bpr + i]
        return (pltpu.make_async_copy(pages_hbm.at[page, pl.ds(0, nc * pt)],
                                      cbuf.at[buf], sem.at[0, buf]),
                pltpu.make_async_copy(
                    pages_hbm.at[page, pl.ds(nc * pt, rows)],
                    pbuf.at[buf], sem.at[1, buf]))

    m_s[...] = jnp.full_like(m_s, NEG_INF)
    l_s[...] = jnp.zeros_like(l_s)
    acc_s[...] = jnp.zeros_like(acc_s)
    t = jax.lax.broadcasted_iota(jnp.int32, (hq, pt), 1)
    # token t's RoPE key sits in packed row t % rows, lanes of t // rows
    row_t = jax.lax.broadcasted_iota(jnp.int32, (pt, lane), 0) // rows
    lane_t = jax.lax.broadcasted_iota(jnp.int32, (pt, lane), 1) // rope
    own = row_t == lane_t

    def score(cps, buf, last):
        cc, cp = cps
        cp.wait()
        kp = pbuf[buf].astype(jnp.float32)                    # (rows, lane)
        kp = jnp.where(own, jnp.concatenate([kp] * (pt // rows), axis=0),
                       0.0)                                   # (pt, lane)
        # q_pe repeated in every RoPE-key position of a lane row
        s = jax.lax.dot_general(qpe_ref[0].astype(jnp.float32), kp,
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        cc.wait()
        for j in range(nc):
            c = cbuf[buf, pl.ds(j * pt, pt)].astype(jnp.float32)  # (pt, lane)
            s = s + jax.lax.dot_general(
                qc_ref[0, j].astype(jnp.float32), c, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        s = jnp.where(t <= last, s * scale, NEG_INF)          # (hq, pt)
        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_s[...] = alpha * l_s[...] + jnp.sum(p, axis=-1, keepdims=True)
        for j in range(nc):
            c = cbuf[buf, pl.ds(j * pt, pt)].astype(jnp.float32)
            acc_s[j] = acc_s[j] * alpha + jax.lax.dot(
                p, c, preferred_element_type=jnp.float32)
        m_s[...] = m_new

    _walk_live_pages((page_ref, blk_ref, nlive_ref, off_ref, nxt_ref,
                      lens_ref), copies, score, bpr=bpr, pt=pt)
    acc_o[0] = acc_s[...]
    m_o[0] = m_s[...]
    l_o[0] = l_s[...]


def mla_decode_stats_fwd(q_c: jax.Array, q_pe: jax.Array, pages: jax.Array,
                         tab: jax.Array, slot_len: jax.Array,
                         slot_valid: jax.Array, first_block, *,
                         page_tokens: int, rope_pack: int, scale: float,
                         interpret: bool = False
                         ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Split-KV statistics of multi-head latent attention, absorbed form,
    read in place from a paged arena of latent pages.

    q_c: (B, H, r), the queries through ``W_UK``; q_pe: (B, H, dr), their
    roped parts.  pages: (n_pages, page_rows, lane), a page's latent rows
    ``c`` in ``r / lane`` blocks of ``page_tokens`` rows (block ``j`` holds
    lanes ``[j*lane, (j+1)*lane)`` of every token's ``c``), then its RoPE
    keys ``rope_pack`` to a row (token ``t`` in row ``t % (page_tokens /
    rope_pack)``, lanes ``[q*dr, (q+1)*dr)``, ``q = t // (page_tokens /
    rope_pack)``).  tab, slot_len, slot_valid, first_block: as
    :func:`paged_decode_stats_fwd`.

    Every head scores ``scale * (q_c . c(t) + q_pe . k_pe(t))`` over one
    shared row a token (MQA over the latent row) and accumulates
    ``sum_t p(t) c(t)``.  The walk over each slot's live pages is
    :func:`paged_decode_stats_fwd`'s.  Returns fp32 ``(acc (B, H, r),
    m (B, H, 1), l (B, H, 1))``; a slot with no live position returns
    ``m = NEG_INF``, ``l = 0``, ``acc = 0``."""
    b, hq, r = q_c.shape
    dr = q_pe.shape[-1]
    n_pages, page_rows, lane = pages.shape
    pt, bpr = page_tokens, tab.shape[1]
    nc, rows = r // lane, page_tokens // rope_pack
    if r % lane or lane != dr * rope_pack or pt % rope_pack \
            or page_rows < nc * pt + rows:
        raise ValueError(f"pages {pages.shape} do not hold {pt} latent rows "
                         f"of {r} and RoPE keys of {dr} in rows of {lane}")
    walk = _live_walk(tab, slot_len, slot_valid, first_block, pt)
    qc = q_c.reshape(b, hq, nc, lane).transpose(0, 2, 1, 3)
    qpe = jnp.tile(q_pe, (1, 1, rope_pack))                 # (B, H, lane)

    kernel = functools.partial(_mla_decode_kernel, scale=scale, pt=pt,
                               bpr=bpr, rope=dr)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, nc, hq, lane), lambda b_, *_: (b_, 0, 0, 0)),
            pl.BlockSpec((1, hq, lane), lambda b_, *_: (b_, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, nc, hq, lane), lambda b_, *_: (b_, 0, 0, 0)),
            pl.BlockSpec((1, hq, 1), lambda b_, *_: (b_, 0, 0)),
            pl.BlockSpec((1, hq, 1), lambda b_, *_: (b_, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, nc * pt, lane), pages.dtype),  # c, double-buffered
            pltpu.VMEM((2, rows, lane), pages.dtype),     # packed k_pe
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((hq, 1), jnp.float32),             # running max m
            pltpu.VMEM((hq, 1), jnp.float32),             # running denom l
            pltpu.VMEM((nc, hq, lane), jnp.float32),      # output accumulator
        ],
    )
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, nc, hq, lane), jnp.float32),
            jax.ShapeDtypeStruct((b, hq, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, hq, 1), jnp.float32),
        ],
        # the slots run in order: each prefetches the next one's first page
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="mla_decode",
    )(*walk, qc, qpe, pages)
    return acc.transpose(0, 2, 1, 3).reshape(b, hq, r), m, l

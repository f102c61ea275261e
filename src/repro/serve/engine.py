"""Paged decode engine: flash-decode attention over the KV page arena.

One jitted step decodes one token for every active slot against the paged
KV cache.  The design commitments, in paper terms:

* **One donated buffer.**  The whole KV cache is the flat page arena from
  :func:`repro.serve.kv.plan_kv_arena`; it is the step's *first* argument
  and is donated, so XLA aliases input to output and the buffer is
  allocated exactly once for the life of the server — the serving analogue
  of the gradient :class:`~repro.mem.arena.CommArena`.
* **Page-parallel decode on the model axis.**  Weights replicate across the
  model axis (decode is α-bound, not FLOP-bound; head-sharding would force
  a collective per projection) and the axis is spent where the memory is:
  each rank scores a static ``blocks_per_rank`` chunk of the page-table
  columns with the paged flash-decode kernel, which reads the live pages
  in place through the table, then the partial softmax statistics merge
  across ranks.  The ``ref`` path gathers the chunk dense, copies it per
  query head and scores it with the jnp oracle.
* **Two collectives per layer per token, fused.**  The cross-rank merge is
  one ``pmax`` of the running max plus ONE fused
  :meth:`Communicator.all_reduce` carrying the rescaled numerator and
  denominator in a single flat buffer — against the naive three
  (max/num/den) of the sequence-sharded path in ``models.attention``.
  With ``model == 1`` both are statically skipped: a single-rank decode
  step lowers to **zero** collectives.  ``dryrun --suite serve`` holds the
  resulting count (``2 · n_layers`` or ``0``) to the optimized HLO exactly.

Admission, eviction and page recycling are host-side (numpy) and change no
traced shape, so the step compiles once per ``(plan, arch)``.

An arena of ``latent`` pages (multi-head latent attention, see
:mod:`repro.serve.kv`) gets its own block: the queries are absorbed
through ``W_UK`` before the ``mla_decode`` kernel scores each slot's live
latent pages in place (the same page walk), ``W_UV`` and ``W_o`` follow,
and an expert layer runs its held experts (:mod:`repro.models.moe`).

Each part of the step runs under a ``jax.named_scope`` of
:data:`STEP_SCOPES` (no layer index), which lands in the compiled
instructions' ``op_name`` metadata; :meth:`PagedDecodeEngine.op_scopes`
maps the instruction names a profiler trace shows to those scopes.  The
host side runs under the ``serve.*`` spans of its ``obs``.
"""

from __future__ import annotations

import collections
import re

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.comm import CommConfig, Communicator
from repro.obs import NULL_OBS
from repro.configs.base import ModelConfig
from repro.kernels import default_interpret
from repro.kernels.flash_decode import ops as fd_ops
from repro.kernels.flash_decode import ref as fd_ref
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models.attention import _merge_heads, _split_heads, padded_heads
from repro.models.common import (apply_rope, dense, embed, glu_mlp, rmsnorm,
                                 unembed)
from repro.runtime.train_step import make_ctx
from repro.serve.kv import KVArenaPlan, KVPageAllocator, PageTable
from repro.sharding import rules as shard_rules


# ---------------------------------------------------------------------------
# prediction layer (read by launch/dryrun --suite serve and bench_serve)
# ---------------------------------------------------------------------------


def predicted_collectives_per_token(plan: KVArenaPlan) -> int:
    """HLO all-reduce ops one decode step lowers to: pmax + one fused LSE
    stats reduce per layer when the model axis is real, else zero."""
    return 2 * plan.n_layers if plan.model_parallel > 1 else 0


def predicted_wire_bytes_per_token(plan: KVArenaPlan, cfg: ModelConfig,
                                   batch: int) -> float:
    """Per-device all-reduce wire bytes of one decode step (ring lower
    bound, ``2(R-1)/R`` hops): the fp32 running max (B·Hq) plus the fused
    numerator+denominator buffer (B·Hq·(D+1)) per layer."""
    r = plan.model_parallel
    if r <= 1:
        return 0.0
    hq = padded_heads(cfg.attn.num_heads)
    hops = 2.0 * (r - 1) / r
    per_layer = (batch * hq + batch * hq * (plan.head_dim + 1)) * 4
    return plan.n_layers * per_layer * hops


# The named scopes of the decode step, in the order a layer runs them.
# ``kv_gather`` and ``gqa_expand`` exist only on the ``ref`` path (the
# kernel reads the pages in place), ``attn_merge`` only with a model axis
# of more than one rank.
STEP_SCOPES = ("embed", "qkv_proj", "kv_write", "kv_gather", "gqa_expand",
               "flash_decode", "attn_merge", "o_proj", "mlp", "lm_head")
# The named scopes of a step over latent pages (multi-head latent
# attention): ``mla_q`` the query and latent projections, RoPE, the latent
# norm and the absorption through ``W_UK``; ``mla_decode`` the kernel (or,
# on the ``ref`` path, the oracle after ``kv_gather``) and its combine;
# ``mla_out`` ``W_UV`` and ``W_o``; a dense layer's MLP ``mlp``, an expert
# layer's ``moe_router`` (with its norm), ``moe_experts`` (the held
# experts) and ``moe_shared`` (the shared experts).
LATENT_STEP_SCOPES = ("embed", "mla_q", "kv_write", "kv_gather", "mla_decode",
                      "mla_out", "mlp", "moe_router", "moe_experts",
                      "moe_shared", "lm_head")
_SCOPES = frozenset(STEP_SCOPES + LATENT_STEP_SCOPES)
_HEADER = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")


def _named_scope(op_name: str) -> str | None:
    parts = [p for p in op_name.split("/") if p in _SCOPES]
    return parts[-1] if parts else None


def instruction_scopes(hlo_text: str) -> dict[str, str]:
    """``{instruction name: scope}`` of a compiled module's text.

    An instruction's scope is the innermost :data:`STEP_SCOPES` (or
    :data:`LATENT_STEP_SCOPES`) name among
    the ``/``-separated parts of its ``op_name``.  The compiler makes some
    instructions with no such name (fusions of a gather's pieces, layout
    copies, prefetches of weights); each of these takes, in this order,
    the scope most of its fused instructions carry, the scope of its
    nearest operand that has one, or that of its nearest user."""
    own: dict[str, str | None] = {}
    refs: dict[str, list[str]] = {}
    calls: dict[str, str] = {}
    by_comp: dict[str, list[str]] = collections.defaultdict(list)
    comp = None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line) if " = " in line else None
        if m is None:
            h = _HEADER.match(line)
            comp = h.group(1) if h else comp
            continue
        name, rest = m.groups()
        body = rest.split(", metadata=", 1)[0]
        op = _OP_NAME.search(rest)
        own[name] = _named_scope(op.group(1)) if op else None
        refs[name] = _REF.findall(body)
        c = _CALLS.search(body)
        if c:
            calls[name] = c.group(1)
        if own[name]:
            by_comp[comp].append(own[name])
    operands = {n: [r for r in rs if r in own] for n, rs in refs.items()}
    users: dict[str, list[str]] = collections.defaultdict(list)
    for n, rs in operands.items():
        for r in rs:
            users[r].append(n)

    def nearest(start, edges):
        seen, queue = {start}, collections.deque(edges.get(start, ()))
        while queue:
            n = queue.popleft()
            if n in seen:
                continue
            if own[n]:
                return own[n]
            seen.add(n)
            queue.extend(edges.get(n, ()))
        return None

    out = {}
    for n, sc in own.items():
        if sc is None and by_comp.get(calls.get(n)):
            sc = collections.Counter(by_comp[calls[n]]).most_common(1)[0][0]
        sc = sc or nearest(n, operands) or nearest(n, users)
        if sc:
            out[n] = sc
    return out


# ---------------------------------------------------------------------------
# paged read/write (device side, fixed shapes)
# ---------------------------------------------------------------------------


def _write_token_kv(pages, plan: KVArenaPlan, layer: int, table, slot_len,
                    slot_valid, k1, v1):
    """Scatter this step's K/V (B, Hkv, 1, D) into each slot's current page.

    The arena is viewed as rows of ``head_dim`` elements, so each update
    is one contiguous D-wide row per (slot, kv head) rather than D scalar
    scatters.  Invalid slots (or unmapped blocks) get an out-of-bounds
    row, which the scatter drops — no branch, no shape change."""
    pt, d, hkv = plan.page_tokens, plan.head_dim, plan.num_kv_heads
    block = slot_len // pt
    within = slot_len % pt
    page = jnp.take_along_axis(table[:, :, layer], block[:, None],
                               axis=1)[:, 0]                       # (B,)
    ok = slot_valid & (page >= 0)
    rows = pages.reshape(-1, d)
    base = page * (plan.page_stride // d) + within                  # (B,)
    ridx = base[:, None] + jnp.arange(hkv)[None, :] * pt           # (B,Hkv)
    ridx = jnp.where(ok[:, None], ridx, rows.shape[0])             # OOB drop
    rows = rows.at[ridx].set(k1[:, :, 0, :].astype(rows.dtype), mode="drop")
    rows = rows.at[ridx + plan.v_offset // d].set(
        v1[:, :, 0, :].astype(rows.dtype), mode="drop")
    return rows.reshape(-1)


def _gather_local_kv(pages, plan: KVArenaPlan, layer: int, table, rank):
    """This rank's chunk of the paged cache as dense (B, Hkv, L_local, D)
    K/V, plus its page-table slice (for validity).  ``rank`` is traced;
    the chunk extent ``blocks_per_rank`` is static.  Whole pages are
    gathered as rows of the (pages, page_stride) view of the arena."""
    bpr, pt, d = plan.blocks_per_rank, plan.page_tokens, plan.head_dim
    hkv = plan.num_kv_heads
    tab = lax.dynamic_slice_in_dim(table[:, :, layer], rank * bpr, bpr,
                                   axis=1)                         # (B, bpr)
    stride = plan.page_stride
    by_page = pages[:plan.n_kv_pages * stride].reshape(-1, stride)
    blk = jnp.take(by_page, jnp.maximum(tab, 0), axis=0)   # (B, bpr, stride)
    b, n = blk.shape[0], hkv * pt * d

    def dense(start):
        return blk[:, :, start:start + n].reshape(b, bpr, hkv, pt, d) \
            .transpose(0, 2, 1, 3, 4).reshape(b, hkv, bpr * pt, d)

    return dense(0), dense(plan.v_offset), tab


def _write_token_latent(pages, plan: KVArenaPlan, layer: int, table,
                        slot_len, slot_valid, c1, k1):
    """Write this step's latent row ``c1`` (B, r) and RoPE key ``k1``
    (B, dr) into each slot's current latent page (layout in
    :mod:`repro.serve.kv`), on the arena's view as rows of ``lane``
    elements: ``r / lane`` whole rows, and the key into its lanes of a
    packed row (the row's other keys, other positions of the same slot,
    are read and written back unchanged).  Invalid slots or unmapped
    blocks write out of bounds, which the scatter drops."""
    pt, lane, pack, dr = (plan.page_tokens, plan.lane, plan.rope_pack,
                          plan.rope_dim)
    nc, krows = plan.head_dim // lane, pt // pack
    block = slot_len // pt
    within = slot_len % pt
    page = jnp.take_along_axis(table[:, :, layer], block[:, None],
                               axis=1)[:, 0]                       # (B,)
    ok = slot_valid & (page >= 0)
    rows = pages.reshape(-1, lane)
    n = rows.shape[0]
    base = page * (plan.page_stride // lane)
    cidx = base[:, None] + jnp.arange(nc)[None, :] * pt + within[:, None]
    cidx = jnp.where(ok[:, None], cidx, n)
    rows = rows.at[cidx].set(
        c1.reshape(-1, nc, lane).astype(rows.dtype), mode="drop")
    kidx = jnp.where(ok, base + nc * pt + within % krows, n)
    old = jnp.take(rows, jnp.minimum(kidx, n - 1), axis=0)         # (B, lane)
    mine = (jnp.arange(lane)[None, :] // dr) == (within // krows)[:, None]
    new = jnp.where(mine, jnp.tile(k1, (1, pack)).astype(rows.dtype), old)
    rows = rows.at[kidx].set(new, mode="drop")
    return rows.reshape(-1)


def _gather_local_latent(pages, plan: KVArenaPlan, layer: int, table, rank):
    """This rank's chunk of the latent pages as dense ``c`` (B, L, r) and
    ``k_pe`` (B, L, dr), plus its page-table slice (the ``ref`` path)."""
    bpr, pt, stride = plan.blocks_per_rank, plan.page_tokens, plan.page_stride
    lane, pack, dr, r = plan.lane, plan.rope_pack, plan.rope_dim, plan.head_dim
    tab = lax.dynamic_slice_in_dim(table[:, :, layer], rank * bpr, bpr,
                                   axis=1)                         # (B, bpr)
    by_page = pages[:plan.n_kv_pages * stride].reshape(-1, stride)
    blk = jnp.take(by_page, jnp.maximum(tab, 0), axis=0)   # (B, bpr, stride)
    b, nc = blk.shape[0], r // lane
    c = blk[:, :, :nc * pt * lane].reshape(b, bpr, nc, pt, lane) \
        .transpose(0, 1, 3, 2, 4).reshape(b, bpr * pt, r)
    k0 = nc * pt * lane
    k = blk[:, :, k0:k0 + pt * dr].reshape(b, bpr, pt // pack, pack, dr) \
        .transpose(0, 1, 3, 2, 4).reshape(b, bpr * pt, dr)
    return c, k, tab


def _local_valid(plan: KVArenaPlan, tab, slot_len, slot_valid, rank):
    """(B, L_local) mask: position exists (≤ current pos, incl. the token
    just written), its block is mapped, and the slot is live."""
    bpr, pt = plan.blocks_per_rank, plan.page_tokens
    blk = rank * bpr + jnp.arange(bpr)
    gpos = blk[:, None] * pt + jnp.arange(pt)[None, :]         # (bpr, Pt)
    ok = gpos[None] <= slot_len[:, None, None]
    ok = ok & (tab >= 0)[:, :, None] & slot_valid[:, None, None]
    return ok.reshape(ok.shape[0], bpr * pt)


# ---------------------------------------------------------------------------
# the step
# ---------------------------------------------------------------------------


def build_paged_decode_step(model, mesh: Mesh, plan: KVArenaPlan, *,
                            attn_impl: str = "kernel",
                            interpret: bool | None = None,
                            donate: bool = True):
    """Returns ``(step, param_specs, state_specs)`` with
    ``step(pages, params, table, token, slot_len, slot_valid) ->
    (logits (B, vocab), pages)``; ``pages`` is donated (argument 0).

    ``params`` must be the full (un-sharded) tree — the engine replicates
    weights over the model axis by design (see module docstring).
    ``attn_impl``: "kernel" scores the live pages in place with the Pallas
    paged flash-decode kernel, "ref" gathers them dense and scores them
    with the jnp oracle (same math and identical collective footprint; the
    dry-run uses "ref" to keep compile times sane).
    """
    if attn_impl not in ("kernel", "ref"):
        raise ValueError(f"attn_impl must be kernel|ref, got {attn_impl!r}")
    cfg = model.cfg
    ctx = make_ctx(mesh)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    r_mesh = sizes.get("model", 1)
    if r_mesh != plan.model_parallel:
        raise ValueError(
            f"plan was laid out for model_parallel={plan.model_parallel} "
            f"but the mesh model axis is {r_mesh}; re-plan with this mesh")
    if plan.kind == "latent":
        return _build_latent_step(model, mesh, plan, ctx, attn_impl,
                                  interpret, donate)
    if plan.page_stride % plan.head_dim or plan.total_elems % plan.head_dim:
        raise ValueError(
            f"KV pages must hold whole head_dim={plan.head_dim} rows "
            f"(page stride {plan.page_stride}, arena {plan.total_elems} "
            f"elements); pick a page_bytes that is a multiple of "
            f"head_dim * itemsize")
    r = plan.model_parallel
    comm = (Communicator(mesh, CommConfig(transport="psum",
                                          data_axes=("model",), channels=1))
            if r > 1 else None)
    cdt = jnp.dtype(cfg.dtype)
    hkv, hd = cfg.attn.num_kv_heads, cfg.attn.head_dim
    true_group = max(cfg.attn.num_heads // hkv, 1)
    bpr, page_rows = plan.blocks_per_rank, plan.page_stride // hd
    interpret = default_interpret() if interpret is None else interpret
    if attn_impl == "kernel" and not interpret:
        fd_ops.check_paged_tiling(hkv, plan.page_tokens, hd,
                                  plan.page_stride)

    def attend(q, pages, layer, table, slot_len, slot_valid):
        rank = ctx.model_index()
        if attn_impl == "kernel":
            with jax.named_scope("flash_decode"):
                tab = lax.dynamic_slice_in_dim(table[:, :, layer], rank * bpr,
                                               bpr, axis=1)       # (B, bpr)
                acc, m, l = fd_ops.paged_decode_stats(
                    q, pages.reshape(plan.n_kv_pages, page_rows, hd), tab,
                    slot_len, slot_valid, rank * bpr, num_kv_heads=hkv,
                    page_tokens=plan.page_tokens, group=true_group,
                    interpret=interpret)
        else:
            with jax.named_scope("kv_gather"):
                k, v, tab = _gather_local_kv(pages, plan, layer, table, rank)
                valid = _local_valid(plan, tab, slot_len, slot_valid, rank)
            with jax.named_scope("gqa_expand"):
                # true-group GQA map (padded q heads clip to the last kv
                # head) — expand kv per q head so the oracle runs group-free
                kv_idx = jnp.clip(jnp.arange(q.shape[1]) // true_group, 0,
                                  hkv - 1)
                k = jnp.take(k, kv_idx, axis=1)
                v = jnp.take(v, kv_idx, axis=1)
            with jax.named_scope("flash_decode"):
                acc, m, l = fd_ref.decode_stats(q, k, v, valid)
        if r == 1:
            with jax.named_scope("flash_decode"):
                return fd_ref.combine([(acc, m, l)]).astype(q.dtype)
        with jax.named_scope("attn_merge"):
            m_g = ctx.pmax(m)
            w = jnp.exp(m - m_g)
            n_num = acc.size
            buf = jnp.concatenate([(acc * w).reshape(-1),
                                   (l * w).reshape(-1)])
            red = comm.all_reduce([buf])[0]
            num = red[:n_num].reshape(acc.shape)
            den = red[n_num:].reshape(l.shape)
            return (num / jnp.maximum(den, 1e-30)).astype(q.dtype)

    def fn(pages, params, table, token, slot_len, slot_valid):
        with jax.named_scope("embed"):
            x = embed(params["embed"], token[:, None], cdt, ctx,
                      cfg.vocab_size)
        posb = slot_len[:, None]                       # per-slot position
        for i, bp in enumerate(params["blocks"]):
            kind = cfg.layer_kind(i)
            pa = bp["attn"]
            with jax.named_scope("qkv_proj"):
                h = rmsnorm(bp["ln1"], x, cfg.norm_eps)
                n_hq = pa["wq"]["w"].shape[1] // hd
                # The barrier keeps the head split's layout from reaching
                # back through each dot to its weight: without it the TPU
                # compiler writes a converted, relaid-out copy of wq, wk
                # and wv every step instead of reading them in place.
                q, k1, v1 = lax.optimization_barrier(
                    (dense(pa["wq"], h, cdt), dense(pa["wk"], h, cdt),
                     dense(pa["wv"], h, cdt)))
                q = _split_heads(q, n_hq)
                k1 = _split_heads(k1, hkv)
                v1 = _split_heads(v1, hkv)
                q = apply_rope(q, posb, cfg.attn.rope_theta)
                k1 = apply_rope(k1, posb, cfg.attn.rope_theta)
            with jax.named_scope("kv_write"):
                pages = _write_token_kv(pages, plan, i, table, slot_len,
                                        slot_valid, k1, v1)
            o = attend(q, pages, i, table, slot_len, slot_valid)
            with jax.named_scope("o_proj"):
                x = x + dense(pa["wo"], _merge_heads(o), cdt).astype(x.dtype)
            if "moe" in bp or "mlp" in bp:
                with jax.named_scope("mlp"):
                    h2 = rmsnorm(bp["ln2"], x, cfg.norm_eps)
                    if kind["mlp"] == "moe":
                        y, _, _ = moe_mod.moe_apply(bp["moe"], h2, cfg.moe,
                                                    cfg.act, ctx=ctx,
                                                    compute_dtype=cdt)
                    else:
                        y = glu_mlp(bp["mlp"], h2, cfg.act, cdt, ctx,
                                    cfg.d_ff)
                    x = x + y.astype(x.dtype)
        with jax.named_scope("lm_head"):
            x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
            if cfg.tie_embeddings:
                logits = unembed(params["embed"], x, cdt)
            else:
                logits = dense(params["lm_head"], x, cdt)
            return logits[:, 0], pages

    return _jit_step(fn, model, mesh, plan, donate)


def _jit_step(fn, model, mesh: Mesh, plan: KVArenaPlan, donate: bool,
              extra_out: tuple = ()):
    """``fn`` as the jitted, shard-mapped step (weights replicated, the
    arena donated); ``extra_out``: specs of outputs after the pages."""
    state_abs = {
        "pages": jax.ShapeDtypeStruct((plan.total_elems,), plan.layout.dtype),
        "page_table": jax.ShapeDtypeStruct(
            (plan.max_seqs, plan.max_blocks, plan.n_layers), jnp.int32),
        "slot_len": jax.ShapeDtypeStruct((plan.max_seqs,), jnp.int32),
        "slot_valid": jax.ShapeDtypeStruct((plan.max_seqs,), jnp.bool_),
    }
    sspecs = shard_rules.decode_state_specs(state_abs, model.cfg, mesh,
                                            plan.max_seqs)
    pspecs = jax.tree.map(lambda _: P(), model.abstract_params())
    sharded = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(sspecs["pages"], pspecs, sspecs["page_table"], P(),
                  sspecs["slot_len"], sspecs["slot_valid"]),
        out_specs=(P(), sspecs["pages"]) + extra_out, check_vma=False)
    step = jax.jit(sharded, donate_argnums=(0,) if donate else ())
    return step, pspecs, sspecs


def _build_latent_step(model, mesh: Mesh, plan: KVArenaPlan, ctx,
                       attn_impl: str, interpret: bool | None, donate: bool):
    """The step over latent pages: multi-head latent attention in its
    absorbed form (``q_nope`` through ``W_UK`` before the kernel, ``W_UV``
    and ``W_o`` after it), a dense MLP or an expert layer told which
    experts it holds (:func:`repro.models.moe.held_experts`).  Its third
    output counts the step's (live token, expert) choices that land on
    held experts, over all expert layers."""
    cfg = model.cfg
    a, moe = cfg.attn, cfg.moe
    if plan.model_parallel > 1:
        raise NotImplementedError(
            "latent pages are served on one model rank (model axis of 1)")
    cdt = jnp.dtype(cfg.dtype)
    pt = plan.page_tokens
    page_rows = plan.page_stride // plan.lane
    sc = mla_mod.scale(a)
    interpret = default_interpret() if interpret is None else interpret
    if attn_impl == "kernel" and not interpret:
        fd_ops.check_latent_tiling(pt, plan.lane, plan.head_dim,
                                   plan.rope_pack, page_rows)

    def attend(q_c, q_pe, pages, layer, table, slot_len, slot_valid):
        if attn_impl == "kernel":
            with jax.named_scope("mla_decode"):
                acc, _, l = fd_ops.mla_decode_stats(
                    q_c, q_pe,
                    pages.reshape(plan.n_kv_pages, page_rows, plan.lane),
                    table[:, :, layer], slot_len, slot_valid, 0,
                    page_tokens=pt, rope_pack=plan.rope_pack, scale=sc,
                    interpret=interpret)
        else:
            with jax.named_scope("kv_gather"):
                c, k_pe, tab = _gather_local_latent(pages, plan, layer,
                                                    table, 0)
                valid = _local_valid(plan, tab, slot_len, slot_valid, 0)
            with jax.named_scope("mla_decode"):
                acc, _, l = mla_mod.latent_stats(q_c, q_pe, c, k_pe, valid,
                                                 sc)
        with jax.named_scope("mla_decode"):
            return acc / jnp.maximum(l, 1e-30)

    def fn(pages, params, table, token, slot_len, slot_valid):
        with jax.named_scope("embed"):
            x = embed(params["embed"], token[:, None], cdt, ctx,
                      cfg.vocab_size)
        posb = slot_len[:, None]                       # per-slot position
        held = jnp.zeros((), jnp.int32)
        for i, bp in enumerate(params["blocks"]):
            pa = bp["mla"]
            with jax.named_scope("mla_q"):
                h = rmsnorm(bp["ln1"], x, cfg.norm_eps)
                q_nope, q_pe = mla_mod.query(pa, h, a, posb, cdt)
                q_c = mla_mod.absorb_query(pa, q_nope[:, :, 0], a, cdt)
                c1, k1 = mla_mod.latent(pa, h, a, posb, cfg.norm_eps, cdt)
            with jax.named_scope("kv_write"):
                pages = _write_token_latent(pages, plan, i, table, slot_len,
                                            slot_valid, c1[:, 0], k1[:, 0])
            o_c = attend(q_c, q_pe[:, :, 0], pages, i, table, slot_len,
                         slot_valid)
            with jax.named_scope("mla_out"):
                y = mla_mod.latent_out(pa, o_c, a, cdt)
                x = x + y[:, None].astype(x.dtype)
            if "mlp" in bp:
                with jax.named_scope("mlp"):
                    h2 = rmsnorm(bp["ln2"], x, cfg.norm_eps)
                    y = glu_mlp(bp["mlp"], h2, cfg.act, cdt)
                    x = x + y.astype(x.dtype)
                continue
            pm = bp["moe"]
            with jax.named_scope("moe_router"):
                h2 = rmsnorm(bp["ln2"], x, cfg.norm_eps)[:, 0]
                w, ids, _ = moe_mod.route(pm, h2, moe, cdt)
                gate = moe_mod.held_gate(w, ids, moe)
                mine = (ids >= moe.first_expert) & (
                    ids < moe.first_expert + moe_mod.held_count(moe))
                held = held + jnp.sum(mine & slot_valid[:, None],
                                      dtype=jnp.int32)
            with jax.named_scope("moe_experts"):
                y = moe_mod.held_experts(pm, h2, gate, cfg.act, cdt)
            if "shared" in pm:
                with jax.named_scope("moe_shared"):
                    y = y + glu_mlp(pm["shared"], h2, cfg.act, cdt)
            x = x + y[:, None].astype(x.dtype)
        with jax.named_scope("lm_head"):
            x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
            if cfg.tie_embeddings:
                logits = unembed(params["embed"], x, cdt)
            else:
                logits = dense(params["lm_head"], x, cdt)
            return logits[:, 0], pages, held

    return _jit_step(fn, model, mesh, plan, donate, extra_out=(P(),))


# ---------------------------------------------------------------------------
# host-side engine: slots, pages, one compile
# ---------------------------------------------------------------------------


def _abstract(a):
    """A step argument's shape, dtype and placement (as lowering sees it)."""
    sharding = a.sharding if getattr(a, "committed", False) else None
    return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)


class PagedDecodeEngine:
    """Slot-indexed decode over the page arena.

    Owns the donated arena buffer, the free-list allocator and the page
    table; :meth:`decode` runs one step for every live slot.  All slot
    management is host numpy with fixed traced shapes — admitting or
    retiring between steps never recompiles."""

    def __init__(self, model, mesh: Mesh, plan: KVArenaPlan, *,
                 attn_impl: str = "kernel", interpret: bool | None = None,
                 donate: bool = True, obs=None):
        self.model, self.mesh, self.plan = model, mesh, plan
        self._routing = []    # latent steps' expert choices, not yet counted
        self.obs = obs
        self.step, self.param_specs, self.state_specs = \
            build_paged_decode_step(model, mesh, plan, attn_impl=attn_impl,
                                    interpret=interpret, donate=donate)
        self.allocator = KVPageAllocator(plan.n_kv_pages)
        self.table = PageTable(plan.max_seqs, plan.max_blocks, plan.n_layers)
        self.slot_len = np.zeros((plan.max_seqs,), np.int32)
        self.slot_valid = np.zeros((plan.max_seqs,), bool)
        # placed as the step returns it, so the first step and every later
        # one (fed the donated output) run one executable
        self.pages = jnp.zeros(
            (plan.total_elems,), plan.layout.dtype,
            device=NamedSharding(mesh, self.state_specs["pages"]))
        self.steps = 0
        self._args = None     # the step's abstract arguments, for op_scopes

    @property
    def obs(self):
        return self._obs

    @obs.setter
    def obs(self, obs) -> None:
        """Swap the telemetry sink; counts the last step still owes go to
        the one being replaced."""
        self._routing_counters(keep=0)
        self._obs = obs if obs is not None else NULL_OBS

    # -- slot management (host side) ----------------------------------------

    def free_slots(self) -> list[int]:
        return [i for i in range(self.plan.max_seqs) if not self.slot_valid[i]]

    def pages_for(self, n_tokens: int) -> int:
        """Worst-case pages a sequence of ``n_tokens`` needs (all layers)."""
        import math as _m

        return _m.ceil(n_tokens / self.plan.page_tokens) * self.plan.n_layers

    def can_admit(self, n_tokens: int) -> bool:
        return (bool(self.free_slots())
                and self.allocator.n_free >= self.pages_for(n_tokens))

    def admit(self, slot: int) -> None:
        if self.slot_valid[slot]:
            raise ValueError(f"slot {slot} is already live")
        with self.obs.span("serve.admit", slot=slot):
            self.slot_len[slot] = 0
            self.slot_valid[slot] = True
            self._ensure_block(slot)
            self.obs.counter("admits")
            self.obs.event("admit", slot=slot,
                           pages_free=self.allocator.n_free)
            self._kv_gauges()

    def retire(self, slot: int) -> None:
        with self.obs.span("serve.retire", slot=slot):
            tokens = int(self.slot_len[slot])
            self.allocator.free(self.table.clear_slot(slot))
            self.slot_valid[slot] = False
            self.slot_len[slot] = 0
            self.obs.counter("retires")
            self.obs.event("retire", slot=slot, tokens=tokens,
                           pages_free=self.allocator.n_free)
            self._kv_gauges()

    def _kv_gauges(self) -> None:
        """Arena health after a slot transition: page occupancy (fraction of
        arena pages mapped) and page waste (fraction of mapped capacity not
        yet holding a token — the partial last page of every live slot)."""
        if not self.obs.enabled:
            return
        alloc, plan = self.allocator, self.plan
        used = alloc.n_total - alloc.n_free
        self.obs.gauge("kv_page_occupancy", used / max(alloc.n_total, 1))
        cap_tokens = (used // plan.n_layers) * plan.page_tokens
        held = int(self.slot_len[self.slot_valid].sum())
        waste = 1.0 - held / cap_tokens if cap_tokens else 0.0
        self.obs.gauge("kv_page_waste", waste)

    def _kv_block_counters(self) -> None:
        """What the step's paged kernel reads of the arena: ``kv_blocks_read``
        counts the live (slot, block, layer) pages it fetches (mapped, of a
        live slot, at or before the slot's position), ``kv_blocks_total``
        every page-table entry it walks (``B × max_blocks × layers``: each
        rank walks ``B × blocks_per_rank × layers``)."""
        if not self.obs.enabled:
            return
        tab, pt = self.table.table, self.plan.page_tokens
        first = np.arange(tab.shape[1])[None, :, None] * pt
        live = ((tab >= 0) & (first <= self.slot_len[:, None, None])
                & self.slot_valid[:, None, None])
        self.obs.counter("kv_blocks_read", int(live.sum()))
        self.obs.counter("kv_blocks_total", tab.size)

    def _routing_counters(self, keep: int = 1) -> None:
        """The expert choices of the steps not yet counted, but for the
        newest ``keep``: ``moe_assignments`` counts each step's (live
        token, expert) pairs over the expert layers,
        ``moe_held_assignments`` those on experts this layer holds.  Read
        two steps late, so that counting never waits for the device, also
        where the caller dispatches a step before it reads the last."""
        if len(self._routing) <= keep:
            return
        cfg = self.model.cfg
        layers = sum(cfg.layer_kind(i)["mlp"] == "moe"
                     for i in range(cfg.num_layers))
        while len(self._routing) > keep:
            live, held = self._routing.pop(0)
            self.obs.counter("moe_assignments", live * cfg.moe.top_k * layers)
            self.obs.counter("moe_held_assignments", int(held))

    def _ensure_block(self, slot: int) -> None:
        blk = int(self.slot_len[slot]) // self.plan.page_tokens
        if self.table.table[slot, blk, 0] < 0:
            self.table.map_block(slot, blk,
                                 self.allocator.alloc(self.plan.n_layers))

    # -- the hot loop --------------------------------------------------------

    def decode(self, params, token) -> jax.Array:
        """One decode step: write ``token[slot]`` at each live slot's
        position, attend over its pages, return logits (B, vocab).
        Invalid slots' rows are garbage by contract."""
        obs = self.obs
        self._routing_counters()
        with obs.span("serve.decode", step=self.steps):
            with obs.span("serve.pages"):
                for s in np.nonzero(self.slot_valid)[0]:
                    self._ensure_block(int(s))
                self._kv_block_counters()
            # the step runs asynchronously while the host goes on mutating
            # its slot arrays, and a host-to-device transfer may alias a
            # numpy buffer (zero-copy on the CPU): hand the step copies
            with obs.span("serve.stage"):
                args = (self.pages, params,
                        jnp.asarray(self.table.table.copy()),
                        jnp.asarray(token, jnp.int32)
                        .reshape(self.plan.max_seqs),
                        jnp.asarray(self.slot_len.copy()),
                        jnp.asarray(self.slot_valid.copy()))
                if self._args is None:
                    self._args = jax.tree.map(_abstract, args)
            with obs.span("serve.dispatch"), self.mesh:
                out = self.step(*args)
            logits, self.pages = out[0], out[1]
            if len(out) > 2 and obs.enabled and self.model.cfg.moe:
                self._routing.append((int(self.slot_valid.sum()), out[2]))
            self.slot_len[self.slot_valid] += 1
        self.steps += 1
        return logits

    def op_scopes(self) -> dict[str, str]:
        """``{instruction name: scope}`` of the compiled decode step (see
        :func:`instruction_scopes`): the names a profiler trace gives the
        step's device operations, mapped to :data:`STEP_SCOPES`.  Lowers
        the step with the arguments of the first :meth:`decode`, so it
        reads the executable that step ran (from the in-memory or the
        persistent compile cache)."""
        if self._args is None:
            raise RuntimeError("op_scopes needs one decode step first")
        with self.mesh:
            text = self.step.lower(*self._args).compile().as_text()
        return instruction_scopes(text)

"""Paged KV cache: the serving generalisation of the huge-page arena.

A page holds one layer's attention state for ``page_tokens`` token
positions of one sequence.  There are two page kinds, by the layers'
attention (:func:`page_kind`):

* ``kv`` (grouped-query attention): K and V per KV head;
* ``latent`` (multi-head latent attention): one latent row per token, the
  normed ``c`` (``kv_lora_rank`` values) and the roped shared key
  ``k_pe`` (``qk_rope_head_dim``), read by every query head.

All pages are laid out in a single flat arena by
:func:`repro.mem.layout.plan_arena` — the same page-quantized placement the
gradient :class:`~repro.mem.arena.CommArena` uses, so every page starts on a
``page_bytes`` boundary (the paper's 2 MiB huge-page granule) and the
padding/waste accounting (:attr:`~repro.mem.layout.ArenaLayout
.padding_fraction`) comes for free.  The arena is allocated **once** and
threaded through the jitted decode step as a **donated** buffer, exactly
like the training arena: no per-step transient KV allocations, XLA aliases
input to output.

In-page element layout (cache dtype, default bf16).  A ``kv`` page::

    [ K: (Hkv, page_tokens, head_dim) ][ V: same ][ page padding ]

A ``latent`` page, in rows of ``lane = min(128, kv_lora_rank)`` elements
(one lane-dense row of the chip's arena view at Moonlight's 512 + 64)::

    [ c[:, j*lane:(j+1)*lane] for j < kv_lora_rank / lane: (page_tokens, lane) each ]
    [ k_pe, pack = lane / rope to a row: (page_tokens / pack, lane) ][ padding ]

where token ``t``'s ``k_pe`` is in row ``t % (page_tokens / pack)``, lanes
``[q * rope, (q + 1) * rope)`` with ``q = t // (page_tokens / pack)``.

Host-side ownership is a free-list :class:`KVPageAllocator` plus a
per-sequence :class:`PageTable` — ``table[slot, block, layer]`` is the page
id backing token positions ``[block*page_tokens, (block+1)*page_tokens)``
of ``slot`` at ``layer`` (``-1`` = unmapped).  The table is a fixed-shape
int32 array, so admission/eviction between decode steps never recompiles.

``max_blocks`` is padded up to a multiple of the mesh's model-axis size:
the paged engine dedicates the model axis to **page-parallel decode** (each
rank scores a static chunk of the block columns), so the column dim must
tile the axis.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.configs.base import ModelConfig
from repro.mem.layout import PAGE_BYTES, ArenaLayout, plan_arena


PAGE_KINDS = ("kv", "latent")


def page_kind(cfg: ModelConfig) -> str:
    """``latent`` for multi-head latent attention, else ``kv``."""
    return "latent" if cfg.attn is not None and cfg.attn.is_mla else "kv"


def kv_page_payload_elems(cfg: ModelConfig, page_tokens: int) -> int:
    """Used elements of one page: K + V of every KV head (``kv``), or one
    latent row and RoPE key a token (``latent``), for one layer's
    ``page_tokens``."""
    a = cfg.attn
    if page_kind(cfg) == "latent":
        return page_tokens * (a.kv_lora_rank + a.qk_rope_head_dim)
    return 2 * a.num_kv_heads * page_tokens * a.head_dim


def _require_pageable(cfg: ModelConfig) -> None:
    """Paged decode covers decoder-only transformers whose every layer is
    global attention of one page kind: grouped-query attention (``kv``
    pages: K and V per KV head) or multi-head latent attention (``latent``
    pages: one latent row and RoPE key a token, shared by the heads).

    Rolling window/chunk caches reuse slots out of order (their validity
    mask depends on the wrap position), which a page table keyed by
    absolute block index cannot express; SSM/hybrid carry non-KV decode
    state.  Every unsupported kind fails loudly here, at plan time, and
    names the kind it met.
    """
    if cfg.attn is None or cfg.family not in ("dense", "moe") \
            or cfg.frontend is not None or cfg.enc_layers:
        raise NotImplementedError(
            f"paged serving holds kv or latent pages of decoder-only "
            f"attention stacks; met family={cfg.family!r}, "
            f"frontend={cfg.frontend!r}, encoder layers={cfg.enc_layers}")
    for i in range(cfg.num_layers):
        kind = cfg.layer_kind(i)
        if kind["mixer"] != "attn" or not kind.get("attn_global", True):
            met = (kind["mixer"] if kind["mixer"] != "attn" else
                   f"local attention (window={cfg.attn.window}, "
                   f"chunk={cfg.attn.chunk})")
            raise NotImplementedError(
                f"paged serving holds kv or latent pages of global attention "
                f"at every layer; layer {i} is {met}")


@dataclass(frozen=True)
class KVArenaPlan:
    """Placement of a serving fleet's KV pages in one flat donated arena."""

    layout: ArenaLayout          # one segment per KV page, equal sizes
    page_tokens: int             # token positions per page
    max_seqs: int                # sequence slots the arena was sized for
    max_blocks: int              # page-table columns (model-axis padded)
    n_layers: int
    num_kv_heads: int
    head_dim: int                # kv: per head; latent: kv_lora_rank
    model_parallel: int          # model-axis size the block dim tiles
    kind: str = "kv"             # page kind: "kv" | "latent"
    rope_dim: int = 0            # latent: qk_rope_head_dim

    # -- shape ---------------------------------------------------------------

    @property
    def n_kv_pages(self) -> int:
        """Allocatable KV pages (arena segments)."""
        return self.layout.n_segments

    @property
    def page_stride(self) -> int:
        """Element stride between consecutive pages (page-quantized)."""
        return self.layout.segments[0].padded if self.layout.segments else 0

    @property
    def payload_elems(self) -> int:
        return self.layout.segments[0].size if self.layout.segments else 0

    @property
    def k_offset(self) -> int:
        return 0

    @property
    def v_offset(self) -> int:
        return self.num_kv_heads * self.page_tokens * self.head_dim

    @property
    def lane(self) -> int:
        """Latent pages: elements in a row of the page layout."""
        return min(128, self.head_dim)

    @property
    def rope_pack(self) -> int:
        """Latent pages: ``k_pe`` rows held in one lane row."""
        return self.lane // self.rope_dim

    @property
    def total_elems(self) -> int:
        return self.layout.total_elems

    @property
    def total_bytes(self) -> int:
        return self.layout.total_bytes

    @property
    def n_arena_pages(self) -> int:
        """Whole ``page_bytes`` allocation granules (huge pages)."""
        return self.layout.n_pages

    @property
    def padding_fraction(self) -> float:
        return self.layout.padding_fraction

    @property
    def blocks_per_rank(self) -> int:
        return self.max_blocks // self.model_parallel

    def page_offset(self, page_id: int) -> int:
        return self.layout.segments[page_id].offset

    def zeros(self) -> jnp.ndarray:
        """The allocate-once donated arena buffer (thread it through the
        jitted step; never reallocate per token)."""
        return jnp.zeros((self.total_elems,), self.layout.dtype)

    def describe(self) -> dict:
        return {
            "page_tokens": self.page_tokens,
            "max_seqs": self.max_seqs,
            "max_blocks": self.max_blocks,
            "n_layers": self.n_layers,
            "num_kv_heads": self.num_kv_heads,
            "head_dim": self.head_dim,
            "model_parallel": self.model_parallel,
            "kind": self.kind,
            "n_kv_pages": self.n_kv_pages,
            "page_stride": self.page_stride,
            "payload_elems": self.payload_elems,
            "total_bytes": self.total_bytes,
            "n_arena_pages": self.n_arena_pages,
            "page_bytes": self.layout.page_bytes,
            "padding_fraction": self.padding_fraction,
            "dtype": jnp.dtype(self.layout.dtype).name,
        }


def plan_kv_arena(cfg: ModelConfig, mesh: Mesh | None = None, *,
                  page_tokens: int = 16, page_bytes: int = PAGE_BYTES,
                  max_seqs: int = 8, max_seq_len: int = 256,
                  cache_dtype=jnp.bfloat16) -> KVArenaPlan:
    """Page-quantized KV arena for up to ``max_seqs`` concurrent sequences
    of up to ``max_seq_len`` tokens.

    Sizing: ``max_seqs * ceil(max_seq_len / page_tokens) * num_layers``
    pages, each the page-aligned slot of one layer's K+V block — the same
    :func:`~repro.mem.layout.plan_arena` placement the gradient arena uses
    (``channel_of = 0`` everywhere: the KV arena is one contiguous span;
    page granularity, not span fusing, is what serving reuses).
    """
    _require_pageable(cfg)
    if page_tokens < 1:
        raise ValueError(f"page_tokens must be >= 1, got {page_tokens}")
    if max_seqs < 1 or max_seq_len < 1:
        raise ValueError(f"max_seqs/max_seq_len must be >= 1, got "
                         f"{max_seqs}/{max_seq_len}")
    mp = 1
    if mesh is not None:
        mp = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
    blocks = math.ceil(max_seq_len / page_tokens)
    max_blocks = math.ceil(blocks / mp) * mp          # tile the model axis
    n_pages = max_seqs * max_blocks * cfg.num_layers
    payload = kv_page_payload_elems(cfg, page_tokens)
    layout = plan_arena([payload] * n_pages, page_bytes=page_bytes,
                        dtype=cache_dtype, channel_of=[0] * n_pages)
    a = cfg.attn
    if page_kind(cfg) == "latent":
        plan = KVArenaPlan(layout=layout, page_tokens=page_tokens,
                           max_seqs=max_seqs, max_blocks=max_blocks,
                           n_layers=cfg.num_layers, num_kv_heads=1,
                           head_dim=a.kv_lora_rank, model_parallel=mp,
                           kind="latent", rope_dim=a.qk_rope_head_dim)
        if plan.head_dim % plan.lane or plan.lane % plan.rope_dim \
                or page_tokens % plan.rope_pack \
                or plan.page_stride % plan.lane:
            raise ValueError(
                f"latent pages of {page_tokens} tokens hold rows of "
                f"{plan.lane} elements: kv_lora_rank={a.kv_lora_rank} and "
                f"the page stride {plan.page_stride} must be whole rows, "
                f"and qk_rope_head_dim={a.qk_rope_head_dim} must divide a "
                f"row and page_tokens / {plan.rope_pack} keys fill a row")
        return plan
    return KVArenaPlan(layout=layout, page_tokens=page_tokens,
                       max_seqs=max_seqs, max_blocks=max_blocks,
                       n_layers=cfg.num_layers,
                       num_kv_heads=a.num_kv_heads,
                       head_dim=a.head_dim, model_parallel=mp)


class KVPageAllocator:
    """LIFO free-list over the arena's KV pages.

    Host-side (numpy ints, no tracing): the scheduler allocates on block
    crossings and recycles on retirement, between jitted decode steps.
    Invariants (pinned by the property tests): a page is never handed out
    twice, ``free`` of a page not currently allocated raises, and
    ``n_free + n_allocated == n_total`` across any alloc/free cycle.
    """

    def __init__(self, n_pages: int):
        if n_pages < 1:
            raise ValueError(f"n_pages must be >= 1, got {n_pages}")
        self.n_total = int(n_pages)
        self._free: list[int] = list(range(n_pages - 1, -1, -1))
        self._allocated: set[int] = set()

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_allocated(self) -> int:
        return len(self._allocated)

    def alloc(self, n: int) -> list[int]:
        """``n`` page ids, or raises if the arena is out of pages (callers
        check :attr:`n_free` first; the scheduler queues instead)."""
        if n < 0:
            raise ValueError(f"cannot allocate {n} pages")
        if n > len(self._free):
            raise MemoryError(f"KV arena out of pages: want {n}, "
                              f"free {len(self._free)}/{self.n_total}")
        out = [self._free.pop() for _ in range(n)]
        self._allocated.update(out)
        return out

    def free(self, pages) -> None:
        for p in pages:
            p = int(p)
            if p not in self._allocated:
                raise ValueError(f"page {p} is not allocated "
                                 f"(double free or foreign id)")
            self._allocated.remove(p)
            self._free.append(p)


class PageTable:
    """Fixed-shape ``(slots, max_blocks, n_layers)`` int32 page map.

    ``-1`` marks an unmapped block; the device-side gather clips ids and
    masks those positions invalid, so a partially filled table is always
    safe to hand to the jitted step.
    """

    def __init__(self, slots: int, max_blocks: int, n_layers: int):
        self.table = np.full((slots, max_blocks, n_layers), -1, np.int32)

    def map_block(self, slot: int, block: int, layer_pages) -> None:
        """Back ``(slot, block)`` with one page per layer."""
        if len(layer_pages) != self.table.shape[2]:
            raise ValueError(f"need {self.table.shape[2]} pages (one per "
                             f"layer), got {len(layer_pages)}")
        if (self.table[slot, block] >= 0).any():
            raise ValueError(f"slot {slot} block {block} already mapped")
        self.table[slot, block] = np.asarray(layer_pages, np.int32)

    def clear_slot(self, slot: int) -> list[int]:
        """Unmap every block of ``slot``; returns the freed page ids."""
        pages = self.table[slot][self.table[slot] >= 0].tolist()
        self.table[slot] = -1
        return pages

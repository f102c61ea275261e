"""Jit'd wrappers for split-KV decode attention with oracle fallback.

``paged_decode_stats`` is what the paged engine calls: partial softmax
statistics read in place from the KV page arena through the page table,
mergeable across ranks with :func:`ref.combine`.  ``flash_decode_stats``
is the same over one dense KV shard, and ``flash_decode`` closes the loop
locally (single shard → normalised output); shapes that do not tile by
the key block fall back to the one-shot oracle.  ``mla_decode_stats`` is
the paged engine's kernel for latent pages (multi-head latent attention).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from repro.kernels.flash_decode import ref
from repro.kernels.flash_decode.flash_decode import (check_latent_tiling,
                                                     check_paged_tiling,
                                                     flash_decode_stats_fwd,
                                                     mla_decode_stats_fwd,
                                                     paged_decode_stats_fwd)


def _expand_gqa(q, k, v):
    group = q.shape[1] // k.shape[1]
    if group > 1:
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
    return k, v


def flash_decode_stats(q: jax.Array, k: jax.Array, v: jax.Array,
                       valid: jax.Array, *, block_k: int = 128,
                       interpret: bool | None = None
                       ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Partial stats (acc, m, l) for q (B,Hq,1,D) over kv (B,Hkv,L,D)."""
    hq, d = q.shape[1], q.shape[3]
    hkv, sk = k.shape[1], k.shape[2]
    bk = min(block_k, sk)
    if sk % bk or d % 8 or hq % hkv:
        ke, ve = _expand_gqa(q, k, v)
        return ref.decode_stats(q, ke, ve, valid != 0)
    interpret = default_interpret() if interpret is None else interpret
    return flash_decode_stats_fwd(q, k, v, valid, block_k=bk,
                                  interpret=interpret)


def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                 valid: jax.Array, *, block_k: int = 128,
                 interpret: bool | None = None) -> jax.Array:
    """Single-shard decode attention output (B, Hq, 1, D)."""
    stats = flash_decode_stats(q, k, v, valid, block_k=block_k,
                               interpret=interpret)
    return ref.combine([stats]).astype(q.dtype)


def paged_decode_stats(q: jax.Array, pages: jax.Array, tab: jax.Array,
                       slot_len: jax.Array, slot_valid: jax.Array,
                       first_block, *, num_kv_heads: int, page_tokens: int,
                       group: int, interpret: bool | None = None
                       ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Partial stats (acc, m, l) for q (B,Hq,1,D) over the live pages of
    ``tab`` in ``pages`` (n_pages, page_rows, D); see
    :func:`paged_decode_stats_fwd`."""
    interpret = default_interpret() if interpret is None else interpret
    return paged_decode_stats_fwd(q, pages, tab, slot_len, slot_valid,
                                  first_block, num_kv_heads=num_kv_heads,
                                  page_tokens=page_tokens, group=group,
                                  interpret=interpret)


def mla_decode_stats(q_c: jax.Array, q_pe: jax.Array, pages: jax.Array,
                     tab: jax.Array, slot_len: jax.Array,
                     slot_valid: jax.Array, first_block, *, page_tokens: int,
                     rope_pack: int, scale: float,
                     interpret: bool | None = None
                     ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Partial stats (acc (B,H,r), m, l) of absorbed latent attention over
    the live latent pages of ``tab``; see :func:`mla_decode_stats_fwd`."""
    interpret = default_interpret() if interpret is None else interpret
    return mla_decode_stats_fwd(q_c, q_pe, pages, tab, slot_len, slot_valid,
                                first_block, page_tokens=page_tokens,
                                rope_pack=rope_pack, scale=scale,
                                interpret=interpret)

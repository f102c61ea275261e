"""Split-KV decode attention.

The paged engine (``serve/engine.py``, ``attn_impl="kernel"``) calls
:func:`paged_decode_stats`, which reads each slot's live KV pages in place
through the page table, one read per kv head for its whole query group.
:func:`flash_decode_stats` is the same statistics over one dense KV shard
(a single-shard library kernel), :func:`flash_decode` its normalised
output, and :func:`combine` merges statistics across shards or ranks.
:func:`mla_decode_stats` reads pages of latent rows (multi-head latent
attention, absorbed form) on the same walk.
"""

from repro.kernels.flash_decode.ops import (flash_decode, flash_decode_stats,
                                            mla_decode_stats,
                                            paged_decode_stats)
from repro.kernels.flash_decode.ref import combine

__all__ = ["flash_decode", "flash_decode_stats", "mla_decode_stats",
           "paged_decode_stats", "combine"]

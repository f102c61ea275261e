"""Explicit ring collectives built from ``lax.ppermute``.

This is the TPU-native reconstruction of the paper's optimised Baidu
all-reduce: the reduction is expressed as an explicit reduce-scatter +
all-gather ring whose *schedule* we control, instead of a single opaque
``lax.psum``.  The paper's techniques map directly:

* **bidirectional rings** — each segment's payload is split in half and the
  halves travel clockwise / counter-clockwise simultaneously, driving both
  directions of every ICI link (the paper's dual-rail usage);
* **chunked multi-channel transfers** — the payload is further split into
  ``chunks`` independent ppermute chains with no data dependencies between
  them, so the async collective-permute DMAs pipeline (the paper's eight
  threaded PSM2 endpoints);
* **fused local reduce** — the per-hop ``acc += recv`` is the paper's
  OpenMP-threaded reduce loop; here a VPU-aligned fused op (optionally the
  ``kernels/reduce_add`` Pallas kernel) with fp32 accumulation;
* **wire codecs** (beyond-paper) — hops can carry bf16 or block-int8
  payloads (``repro.comm.wire_codec``), shrinking collective bytes.

All functions operate on *flat, pre-padded* 1-D buffers inside a
``shard_map`` manual context (``core.bucketing`` produces those buffers).
Loops over the ``p - 1`` ring steps are deliberately unrolled so the compiled
HLO exposes every collective-permute to the scheduler and to our roofline
collective-byte accounting.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.topology import ring_perm

LocalAdd = Callable[[jax.Array, jax.Array], jax.Array]


@dataclass(frozen=True)
class RingConfig:
    """Static schedule knobs (compile-time; the paper's 'guaranteed' ethos)."""

    chunks: int = 1
    bidirectional: bool = True
    wire_dtype: str | None = None      # None = carry accum dtype on the wire
    accum_dtype: str = "float32"
    local_op: str = "jnp"              # "jnp" | "pallas" (kernels/reduce_add)
    codec: str | None = None           # None | "int8" (per-hop block codec)
    codec_block: int = 512

    def make_codec(self):
        # lazy: repro.comm.wire_codec is the codec's first-class home, and
        # importing repro.comm at module level would close a cycle through
        # repro.comm.api -> repro.core.ring
        from repro.comm.wire_codec import make_codec

        return make_codec(self.codec, wire_dtype=self.wire_dtype,
                          block=self.codec_block)

    @property
    def channel_divisor(self) -> int:
        """Per-segment width divisor imposed by channels + codec blocks."""
        d = self.chunks * (2 if self.bidirectional else 1)
        if self.codec is not None:
            d *= self.codec_block
        return d

    def flat_divisor(self, axis_sizes: Sequence[int]) -> int:
        """Flat-buffer length divisor for a (possibly hierarchical) schedule.

        RS over the innermost axis hands ``L / p`` to the next level, so the
        requirement composes multiplicatively across axes.
        """
        d = 1
        for p in axis_sizes:
            d *= p * self.channel_divisor
        return max(d, 1)


def _resolve_local_add(cfg: RingConfig) -> LocalAdd:
    accum = jnp.dtype(cfg.accum_dtype)
    if cfg.local_op == "pallas":
        from repro.kernels.reduce_add import ops as ra_ops

        return functools.partial(ra_ops.add_accum, accum_dtype=accum)

    def _add(a: jax.Array, b: jax.Array) -> jax.Array:
        return a.astype(accum) + b.astype(accum)

    return _add


def _tree_ppermute(payload, axis: str, perm):
    return jax.tree.map(lambda a: lax.ppermute(a, axis, perm), payload)


# ---------------------------------------------------------------------------
# single-direction primitives on contiguous (p * s,) buffers
# ---------------------------------------------------------------------------


def _rs_1d(x: jax.Array, axis: str, direction: int, cfg: RingConfig,
           local_add: LocalAdd, codec) -> jax.Array:
    """Ring reduce-scatter; device ``r`` ends owning the full sum of segment
    ``r`` (i.e. ``x[r*s:(r+1)*s]`` summed over the axis)."""
    accum = jnp.dtype(cfg.accum_dtype)
    p = lax.axis_size(axis)
    if p == 1:
        return x.astype(accum)
    r = lax.axis_index(axis)
    seg = x.shape[0] // p
    xs = x.reshape(p, seg)
    perm = ring_perm(p, direction)

    # Ownership offset chosen so the final fully-reduced segment is ``r``.
    off = -direction
    idx0 = (r + off) % p
    acc = lax.dynamic_index_in_dim(xs, idx0, axis=0, keepdims=False)
    acc = acc.astype(accum)
    for s in range(p - 1):
        recv = _tree_ppermute(codec.encode(acc), axis, perm)
        nxt = (r + off - (s + 1) * direction) % p
        mine = lax.dynamic_index_in_dim(xs, nxt, axis=0, keepdims=False)
        acc = local_add(codec.decode(recv), mine)
    return acc


def _ag_1d(shard: jax.Array, axis: str, direction: int, codec) -> jax.Array:
    """Ring all-gather of per-device segment ``r`` into the full (p*s,) buffer.

    The payload is encoded *once* at the source and forwarded verbatim, so a
    lossy codec costs a single quantisation (no per-hop compounding).
    """
    p = lax.axis_size(axis)
    if p == 1:
        return shard
    r = lax.axis_index(axis)
    perm = ring_perm(p, direction)
    payload = codec.encode(shard)
    outs = jax.tree.map(
        lambda a: lax.dynamic_update_index_in_dim(
            jnp.zeros((p,) + a.shape, a.dtype), a, r, axis=0),
        payload)
    cur = payload
    for s in range(p - 1):
        cur = _tree_ppermute(cur, axis, perm)
        idx = (r - (s + 1) * direction) % p
        outs = jax.tree.map(
            lambda o, c: lax.dynamic_update_index_in_dim(o, c, idx, axis=0),
            outs, cur)
    decoded = jax.vmap(codec.decode)(outs)
    return decoded.reshape(-1).astype(shard.dtype)


# ---------------------------------------------------------------------------
# multi-channel (bidirectional x chunked) schedules
# ---------------------------------------------------------------------------


def _channel_slices(seg: int, cfg: RingConfig) -> list[tuple[int, int, int]]:
    """(start, width, direction) channel layout of one owned segment."""
    w = seg // cfg.chunks
    out = []
    for c in range(cfg.chunks):
        base = c * w
        if cfg.bidirectional:
            h = w // 2
            out.append((base, h, +1))
            out.append((base + h, w - h, -1))
        else:
            out.append((base, w, +1))
    return out


def _materialise(flat: jax.Array, p: int) -> jax.Array:
    """The flat ring buffer as its own array, unfused with its producer or
    consumer.

    Fused with the reshapes between a tiled 2-D parameter and the flat
    buffer, the channel slicing (reduce-scatter) and interleave
    (all-gather) take the TPU compiler minutes at published widths: RS + AG
    of a 128256x2048 embedding for a v5e:2x2 took 400 s, and 2 s with the
    buffer materialised on both sides.  A ring of one device exchanges
    nothing and compiles in normal time, so its program is left as is."""
    return lax.optimization_barrier(flat) if p > 1 else flat


def _check_divisible(seg: int, cfg: RingConfig) -> None:
    if seg % (cfg.channel_divisor or 1) != 0:
        raise ValueError(
            f"segment {seg} not divisible by channel divisor "
            f"{cfg.channel_divisor} (chunks={cfg.chunks}, "
            f"bidirectional={cfg.bidirectional}, codec={cfg.codec})")


def ring_reduce_scatter(x: jax.Array, axis: str, cfg: RingConfig = RingConfig()) -> jax.Array:
    """Multi-channel ring reduce-scatter of a flat buffer.

    ``x``: (L,), ``L % (p * channel_divisor) == 0``.  Returns device ``r``'s
    fully-reduced segment ``x[r*L/p:(r+1)*L/p]`` in ``cfg.accum_dtype``.
    """
    p = lax.axis_size(axis)
    L = x.shape[0]
    if L % max(p, 1) != 0:
        raise ValueError(f"flat length {L} not divisible by ring size {p}")
    seg = L // p
    _check_divisible(seg, cfg)
    local_add = _resolve_local_add(cfg)
    codec = cfg.make_codec()
    xs = _materialise(x, p).reshape(p, seg)
    shards = []
    for (start, width, direction) in _channel_slices(seg, cfg):
        part = lax.slice_in_dim(xs, start, start + width, axis=1)
        shards.append(_rs_1d(part.reshape(-1), axis, direction, cfg,
                             local_add, codec))
    return jnp.concatenate(shards) if len(shards) > 1 else shards[0]


def ring_all_gather(shard: jax.Array, axis: str, cfg: RingConfig = RingConfig()) -> jax.Array:
    """Inverse of :func:`ring_reduce_scatter` (same channel layout)."""
    seg = shard.shape[0]
    _check_divisible(seg, cfg)
    p = lax.axis_size(axis)
    codec = cfg.make_codec()
    gathered = []  # (p, width) blocks in channel order
    for (start, width, direction) in _channel_slices(seg, cfg):
        part = lax.slice_in_dim(shard, start, start + width, axis=0)
        gathered.append(_ag_1d(part, axis, direction, codec).reshape(p, width))
    blocks = jnp.concatenate(gathered, axis=1) if len(gathered) > 1 else gathered[0]
    return _materialise(blocks.reshape(-1), p)


def ring_all_reduce(x: jax.Array, axis: str, cfg: RingConfig = RingConfig()) -> jax.Array:
    """Bandwidth-optimal all-reduce: reduce-scatter followed by all-gather."""
    shard = ring_reduce_scatter(x, axis, cfg)
    return ring_all_gather(shard, axis, cfg)


# ---------------------------------------------------------------------------
# multi-axis (pod-aware) schedules
# ---------------------------------------------------------------------------


def hierarchical_all_reduce(x: jax.Array, axes: Sequence[str],
                            cfg: RingConfig = RingConfig()) -> jax.Array:
    """Pod-aware all-reduce: RS over the innermost (fast, intra-pod) axis,
    recurse over the outer axes on the 1/p shard, then AG back.

    Cross-pod traffic shrinks by the intra-pod axis size versus a flat
    schedule — the paper's 'drive the fat local links concurrently' insight
    applied across the pod boundary.
    """
    if len(axes) == 0:
        return x
    if len(axes) == 1:
        return ring_all_reduce(x, axes[0], cfg)
    inner, outer = axes[0], axes[1:]
    shard = ring_reduce_scatter(x, inner, cfg)
    shard = hierarchical_all_reduce(shard, outer, cfg)
    return ring_all_gather(shard, inner, cfg)


def flat_all_reduce(x: jax.Array, axes: Sequence[str],
                    cfg: RingConfig = RingConfig()) -> jax.Array:
    """Naive multi-axis schedule: full-size ring all-reduce per axis in turn.

    This is the multi-pod *baseline*: every byte crosses the inter-pod links
    at full size.  Kept for §Perf before/after comparisons.
    """
    for axis in axes:
        x = ring_all_reduce(x, axis, cfg)
    return x


# ---------------------------------------------------------------------------
# all-to-all (expert-parallel dispatch/combine)
# ---------------------------------------------------------------------------


def ring_all_to_all(x: jax.Array, axis: str, *, split_axis: int,
                    concat_axis: int) -> jax.Array:
    """Explicit all-to-all built from ``p - 1`` pairwise ppermute hops.

    Semantics match ``lax.all_to_all(..., tiled=True)``: ``x`` is split into
    ``p`` equal blocks along ``split_axis``; block ``j`` travels to device
    ``j``; the received blocks (one per source, in source order) are
    concatenated along ``concat_axis``.

    Hop ``s`` ships each device's block for destination ``(r + s) % p`` via
    the uniform shift permutation ``r -> (r + s) % p`` — every hop drives all
    links concurrently (the paper's concurrency-through-the-stack pattern)
    and each block crosses the wire exactly once, so per-device wire traffic
    is ``(p - 1)/p`` of the payload in ``p - 1`` messages.

    Every op here is linear (slice/stack/roll/ppermute), so the autodiff
    transpose is the exact inverse all-to-all — no custom VJP needed.
    """
    p = lax.axis_size(axis)
    n = x.shape[split_axis]
    if n % max(p, 1) != 0:
        raise ValueError(
            f"all_to_all split dim {n} not divisible by axis size {p}")
    if p == 1:
        return x
    blk = n // p
    blocks = [lax.slice_in_dim(x, j * blk, (j + 1) * blk, axis=split_axis)
              for j in range(p)]
    xs = jnp.stack(blocks, axis=0)                       # (p_dst, ...)
    r = lax.axis_index(axis)
    # z[s] = block destined for rank (r + s) % p (rank-dependent shift of a
    # traced amount — roll keeps this inside one fused gather).
    z = jnp.roll(xs, -r, axis=0)
    recv = [z[0]]                                        # own block, hop 0
    for s in range(1, p):
        perm = [(src, (src + s) % p) for src in range(p)]
        recv.append(lax.ppermute(z[s], axis, perm))
    stack = jnp.stack(recv, axis=0)                      # stack[s] <- rank (r - s) % p
    # Reorder hop order -> source order: w[j] = stack[(r - j) % p].
    w = jnp.roll(stack[::-1], r + 1, axis=0)
    return jnp.concatenate([w[j] for j in range(p)], axis=concat_axis)

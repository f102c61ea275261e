"""The Communicator: every collective in the system behind one object.

The paper's central technique is concurrency through multiple independent
communicators (multi-rail PSM2 endpoints) over guaranteed large buffers.
:class:`Communicator` makes that a first-class object: constructed once from
``(mesh, CommConfig)``, it owns

* the **transport** — a registered collective schedule
  (:mod:`repro.comm.registry`) whose capabilities are checked here, at
  construction, so an invalid combination never reaches trace time;
* the **bucketer** — fused, alignment-guaranteed flat buffers
  (:mod:`repro.core.bucketing`);
* the **virtual channels** — ``cfg.channels`` independent rails that the
  bucket list is striped across (:func:`repro.comm.plan.assign_channels`).
  ``channels == 0`` leaves every bucket an independent collective (the
  scheduler free-for-all); ``channels == N`` guarantees exactly N rails,
  each issuing its buckets in FIFO order with no cross-rail dependencies —
  the multi-rail analogue as a config knob instead of a code path.

Collective methods (``all_reduce`` / ``reduce_scatter`` / ``all_gather`` /
``halo_exchange``) run *inside* a fully-manual ``shard_map``; ``reduce`` is
the SPMD convenience wrapper that opens one for you.  ``GradientReducer``
(:mod:`repro.core.reducer`) survives as a thin deprecated shim over this
class.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Sequence, TYPE_CHECKING

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.comm.plan import (A2APlan, ChannelAssignment, CommPlan,
                             HaloChannel, HaloPlan, assign_channels)
from repro.comm.registry import Transport, get_transport
from repro.comm.schedule import (CommSchedule, build_halo_schedule,
                                 build_moe_schedule, build_schedule,
                                 halo_units)
from repro.core.bucketing import BucketPlan, GradientBucketer
from repro.comm.wire_codec import ErrorFeedback
from repro.core.halo import HaloSpec, halo_exchange as _halo_exchange
from repro.core.ring import RingConfig
from repro.core.topology import order_token, reduce_axes_of

if TYPE_CHECKING:  # repro.mem is imported lazily (it imports comm.schedule)
    from repro.mem.arena import CommArena, QuantCommArena
    from repro.mem.layout import ArenaLayout, QuantArenaLayout

# NOTE: the legacy ``POLICY_TO_TRANSPORT`` table and
# ``comm_config_from_policy`` live with the rest of the string-policy shim
# in :mod:`repro.core.reducer` (re-exported from :mod:`repro.comm` for
# compatibility).


@dataclass(frozen=True)
class CommConfig:
    """Static (compile-time) description of the communication substrate."""

    transport: str = "ring_hier"
    data_axes: tuple[str, ...] = ("pod", "data")
    bucket_bytes: int = 4 * 2**20
    page_bytes: int = 2 * 2**20    # arena quantization granule (huge page)
    channels: int = 0              # 0 = unconstrained; N = N guaranteed rails
    chunks: int = 2                # per-segment ppermute chains (ring only)
    bidirectional: bool = True
    wire_dtype: str | None = None
    wire_codec: str | None = None  # "int8": quantized wire + arena codec
    codec_block: int = 512
    local_op: str = "jnp"          # "jnp" | "pallas" (kernels/reduce_add)
    mean: bool = True
    fuse: bool = True              # False: per-tensor collectives, no buckets

    def ring_config(self, codec: str | None = None) -> RingConfig:
        return RingConfig(chunks=self.chunks, bidirectional=self.bidirectional,
                          wire_dtype=self.wire_dtype, local_op=self.local_op,
                          codec=codec, codec_block=self.codec_block)


class Communicator:
    """Channelized collectives over the data axes of ``mesh``."""

    def __init__(self, mesh: Mesh, cfg: CommConfig = CommConfig()):
        spec, cls = get_transport(cfg.transport)   # unknown -> ValueError
        if cfg.wire_dtype not in spec.wire_dtypes:
            raise ValueError(
                f"transport {cfg.transport!r} does not support "
                f"wire_dtype={cfg.wire_dtype!r} (allowed: {spec.wire_dtypes})")
        if cfg.channels < 0:
            raise ValueError(f"channels must be >= 0, got {cfg.channels}")
        if cfg.chunks < 1:
            raise ValueError(f"chunks must be >= 1, got {cfg.chunks}")
        if not cfg.fuse and spec.supports_rs:
            # ring schedules need the bucketer's alignment guarantees;
            # unfused (per-tensor) mode is only safe on native collectives
            raise ValueError(
                f"transport {cfg.transport!r} requires fused aligned buckets "
                f"(fuse=True); only native transports support fuse=False")
        self.mesh = mesh
        self.cfg = cfg
        self.spec = spec
        self.axes = reduce_axes_of(mesh.axis_names, cfg.data_axes)
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        self.axis_sizes = tuple(sizes[a] for a in self.axes)
        self.world = 1
        for s in self.axis_sizes:
            self.world *= s
        codec = (cfg.wire_codec if cfg.wire_codec is not None
                 else spec.codec)
        if codec not in (None, "int8"):
            raise ValueError(f"unknown wire_codec {codec!r} "
                             f"(supported: 'int8')")
        if cfg.wire_codec is not None and cfg.wire_dtype is not None:
            raise ValueError("wire_codec and wire_dtype are exclusive wire "
                             "formats; set at most one")
        self.codec = codec
        # codec-capable (ring-family) transports carry the int8 payload on
        # every hop; others (psum) reduce locally-dequantized fp32 spans,
        # so their ring config stays lossless and the wire is priced fp32
        self._ring_cfg = cfg.ring_config(
            codec=codec if spec.supports_codec else None)
        self.transport: Transport = cls(self.axes, self._ring_cfg)
        pad = self.transport.flat_divisor(self.axis_sizes)
        if codec is not None:
            # quantized segments hold whole codec blocks even when the
            # transport's own divisor (e.g. psum) does not include them
            pad = math.lcm(pad, cfg.codec_block)
        self.bucketer = GradientBucketer(bucket_bytes=cfg.bucket_bytes,
                                         pad_multiple=pad)
        self._ef = (ErrorFeedback(self._ring_cfg.make_codec())
                    if self._ring_cfg.codec is not None else None)

    # -- layout / planning ---------------------------------------------------

    @property
    def ordered_axes(self) -> tuple[str, ...]:
        """Innermost (fastest / intra-pod) axis first."""
        return self.transport.ordered_axes

    def stripe(self, bucket_sizes: Sequence[int]
               ) -> tuple[ChannelAssignment, ...]:
        """Partition a bucket list across the virtual channels.

        With ``channels == 0`` every bucket gets its own channel (fully
        independent collectives); otherwise exactly ``cfg.channels`` rails.
        """
        n = self.cfg.channels if self.cfg.channels >= 1 else max(len(bucket_sizes), 1)
        return assign_channels(bucket_sizes, n)

    def plan(self, tree) -> CommPlan:
        """Full communication plan for one gradient-shaped pytree, including
        the page-quantized :class:`~repro.mem.layout.ArenaLayout` the arena
        mode would reduce out of (pages, padding overhead, and the fused
        α/β cost where padding bytes cross the wire too)."""
        bplan = self.bucketer.plan(tree)
        chans = self.stripe(bplan.bucket_sizes)
        n = max(bplan.used_elems, 1)
        codec = self._ring_cfg.make_codec()
        wire_per_elem = codec.wire_bytes(n) / n
        bytes_dev = self.transport.predicted_bytes_per_device(
            bplan.used_elems, self.axis_sizes)
        msgs_per_unit = self.transport.predicted_messages_per_device(
            self.axis_sizes)
        # silent layout: plan() runs for every dry-run/roofline cell; the
        # oversized-leaf warning belongs to actual arena construction
        layout = self.arena_layout(tree, warn=False, _chans=chans)
        # quantized arenas move their (padded) payload elements at the
        # codec's bytes/elem; the trailing scale segment never travels as
        # a unit — scales ride each span's hop payload (priced by the
        # codec's wire_bytes) or stay local under fp32-wire transports
        wire_elems = getattr(layout, "payload_elems", layout.total_elems)
        arena_bytes = self.transport.predicted_bytes_per_device(
            wire_elems, self.axis_sizes)
        return CommPlan(transport=self.cfg.transport, axes=self.axes,
                        axis_sizes=self.axis_sizes, bucket_plan=bplan,
                        channels=chans, wire_bytes_per_elem=wire_per_elem,
                        bytes_per_device=bytes_dev,
                        messages_per_device=msgs_per_unit * bplan.n_buckets,
                        arena_layout=layout,
                        arena_bytes_per_device=arena_bytes,
                        arena_messages_per_device=(msgs_per_unit
                                                   * layout.n_spans),
                        wire_codec=self.codec,
                        codec_block=self.cfg.codec_block)

    def arena_layout(self, tree, *, warn: bool = True,
                     _chans: tuple[ChannelAssignment, ...] | None = None
                     ) -> "ArenaLayout | QuantArenaLayout":
        """The page-quantized arena placement of ``tree``'s buckets:
        segment offsets/sizes quantized to ``cfg.page_bytes`` (lcm'd with
        the transport's flat divisor so fused spans stay reduce-scatter
        legal), segments grouped into one contiguous span per virtual
        channel.  Under a wire codec this is the int8
        :class:`~repro.mem.layout.QuantArenaLayout` (payload + trailing
        scale segment).  (``bucketer.plan`` is signature-cached, so
        repeated calls on the same tree shape replan nothing; ``_chans``
        lets :meth:`plan` reuse its striping.)"""
        from repro.mem.layout import (arena_from_bucket_plan,
                                      quant_arena_from_bucket_plan)

        bplan = self.bucketer.plan(tree)
        chans = (_chans if _chans is not None
                 else self.stripe(bplan.bucket_sizes))
        chan_of = [0] * bplan.n_buckets
        for a in chans:
            for b in a.buckets:
                chan_of[b] = a.channel
        if self.codec is not None:
            return quant_arena_from_bucket_plan(
                bplan, page_bytes=self.cfg.page_bytes,
                block=self.cfg.codec_block, channel_of=chan_of,
                pad_multiple=self.bucketer.pad_multiple,
                bucket_bytes=self.cfg.bucket_bytes, warn_oversized=warn)
        return arena_from_bucket_plan(
            bplan, page_bytes=self.cfg.page_bytes, channel_of=chan_of,
            pad_multiple=self.bucketer.pad_multiple,
            bucket_bytes=self.cfg.bucket_bytes, warn_oversized=warn)

    def arena(self, tree) -> "CommArena | QuantCommArena":
        """A :class:`~repro.mem.arena.CommArena` (or
        :class:`~repro.mem.arena.QuantCommArena` under a wire codec) over
        :meth:`arena_layout`; the pack/unpack implementation follows
        ``cfg.local_op`` (the same knob that selects the Pallas ring-step
        accumulate)."""
        from repro.mem.arena import CommArena, QuantCommArena

        impl = "pallas" if self.cfg.local_op == "pallas" else "jnp"
        if self.codec is not None:
            return QuantCommArena(self.arena_layout(tree), impl=impl)
        return CommArena(self.arena_layout(tree), impl=impl)

    # -- channelized execution (inside a fully-manual shard_map) -------------

    def _run_striped(self, op, items: list) -> list:
        """Apply ``op`` to every flat buffer, honouring channel striping:
        buffers on the same rail are chained (``order_token``, so each rail
        issues FIFO), rails stay independent."""
        if self.cfg.channels < 1:
            return [op(x) for x in items]
        out: list = [None] * len(items)
        for assignment in self.stripe([int(x.shape[0]) for x in items]):
            dep = None
            for i in assignment.buckets:
                y = op(order_token(dep, items[i]))
                dep = y.reshape(-1)[0]
                out[i] = y
        return out

    def all_reduce(self, buckets: list) -> list:
        """Sum each flat bucket over the data axes (no mean)."""
        return self._run_striped(self.transport.all_reduce, buckets)

    def reduce_scatter(self, buckets: list) -> list:
        """Sum-and-shard each flat bucket (inner axis segments first)."""
        if not self.spec.supports_rs:
            raise ValueError(
                f"transport {self.cfg.transport!r} does not support "
                f"reduce-scatter (supports_rs=False)")
        return self._run_striped(self.transport.reduce_scatter, buckets)

    def all_gather(self, shards: list) -> list:
        """Inverse of :meth:`reduce_scatter` (same ownership layout)."""
        if not self.spec.supports_rs:
            raise ValueError(
                f"transport {self.cfg.transport!r} does not support "
                f"all-gather (supports_rs=False)")
        return self._run_striped(self.transport.all_gather, shards)

    def gather_flat(self, shard: jax.Array, *, native: bool = False) -> jax.Array:
        """Per-axis all-gather of one flat shard (FSDP weight path).

        ``native=True`` emits one XLA all-gather op per axis (its autodiff
        transpose is ``psum_scatter``); otherwise the transport's unrolled
        ring schedule is used (transpose == ring reduce-scatter-sum)."""
        if native:
            for ax in self.axes:               # outermost first
                shard = lax.all_gather(shard, ax, tiled=True)
            return shard
        return self.transport.all_gather(shard)

    @property
    def halo_chunks(self) -> int:
        """Pieces each face splits into under the ``chunked`` schedule:
        the channel knob when set, else 4 (the paper's threaded default).
        Single source of the fallback for the executor, the prediction
        layers, and the benchmarks."""
        return self.cfg.channels if self.cfg.channels >= 1 else 4

    def _halo_schedule_name(self, schedule: str | None) -> str:
        return schedule if schedule is not None else (
            "chunked" if self.cfg.channels >= 2 else "concurrent")

    def halo_exchange(self, x: jax.Array, specs: Sequence[HaloSpec], *,
                      schedule: str | None = None) -> dict:
        """Cartesian halo exchange sharing the communicator's channel knob:
        under ``chunked``, ``channels >= 2`` splits every face across that
        many independent rails (the paper's threaded multi-EP columns);
        under ``overlap``, whole faces are striped across the ``channels``
        guaranteed rails with per-rail FIFO order — the same rail rule as
        :meth:`reduce_scheduled` — so interior stencil compute can hide the
        transfers (see :mod:`repro.stencil.op`)."""
        return _halo_exchange(x, specs,
                              schedule=self._halo_schedule_name(schedule),
                              chunks=self.halo_chunks,
                              channels=self.cfg.channels)

    def halo_schedule(self, x_shape: Sequence[int], specs: Sequence[HaloSpec],
                      *, schedule: str | None = None,
                      itemsize: int = 4) -> CommSchedule:
        """The issue slots :meth:`halo_exchange` would execute for one local
        shard of ``x_shape`` — halo overlap as a first-class
        :class:`~repro.comm.schedule.CommSchedule`, exactly like bucket
        reduction (its ``overlap_fraction`` feeds the roofline's
        ``t_exposed_collective``)."""
        sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        return build_halo_schedule(specs, x_shape,
                                   schedule=self._halo_schedule_name(schedule),
                                   channels=self.cfg.channels,
                                   chunks=self.halo_chunks,
                                   itemsize=itemsize, axis_sizes=sizes)

    def halo_plan(self, x_shape: Sequence[int], specs: Sequence[HaloSpec], *,
                  schedule: str | None = None, itemsize: int = 4) -> HaloPlan:
        """Halo bytes per direction × channel for one exchange — the
        :class:`~repro.comm.plan.HaloPlan` analogue of :meth:`plan`, read by
        the dry-run's stencil suite and ``benchmarks/bench_cg.py``."""
        sched = self.halo_schedule(x_shape, specs, schedule=schedule,
                                   itemsize=itemsize)
        sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        keys, _ = halo_units(specs, x_shape, schedule=sched.policy,
                             chunks=self.halo_chunks,
                             itemsize=itemsize, axis_sizes=sizes)
        by_channel: dict[int, list[int]] = {}
        for slot in sched.slots:
            by_channel.setdefault(slot.channel, []).extend(slot.bucket_ids)
        chans = tuple(HaloChannel(c, tuple(sorted(u)), sum(
            sched.bucket_sizes[i] for i in u)) for c, u in
            sorted(by_channel.items()))
        return HaloPlan(
            schedule=sched.policy,
            axes=tuple(s.axis for s in specs),
            axis_sizes=tuple(sizes.get(s.axis, 1) for s in specs),
            local_shape=tuple(int(n) for n in x_shape),
            halos=tuple(s.halo for s in specs),
            unit_keys=tuple(keys),
            unit_bytes=sched.bucket_sizes,
            channels=chans,
            overlap_fraction=sched.overlap_fraction,
        )

    # -- all-to-all (expert-parallel dispatch/combine) -----------------------

    def _a2a_axis(self) -> str:
        if len(self.axes) != 1:
            raise ValueError(
                f"all_to_all needs exactly one comm axis, got {self.axes}; "
                f"construct the Communicator with data_axes=('model',) (or "
                f"the single EP axis)")
        if not self.spec.supports_a2a:
            raise ValueError(
                f"transport {self.cfg.transport!r} does not support "
                f"all-to-all (supports_a2a=False); use 'a2a', a ring "
                f"transport, or 'psum' (honest replicated fallback)")
        return self.axes[0]

    def a2a_rails(self, shape: Sequence[int]) -> int:
        """Independent channel rails one all-to-all of ``shape`` splits into.

        The payload is striped along its last (feature) dimension —
        ``cfg.channels`` rails when it divides evenly, else a single rail.
        Each rail is an independent collective (its own ppermute chain /
        HLO all-to-all op), the multi-EP concurrency knob applied to
        dispatch.
        """
        c = self.cfg.channels
        if c <= 1:
            return 1
        return c if int(shape[-1]) % c == 0 else 1

    def all_to_all(self, x: jax.Array, *, split_axis: int,
                   concat_axis: int) -> jax.Array:
        """Channelized tiled all-to-all over the single comm axis.

        Semantics of ``lax.all_to_all(..., tiled=True)``: ``x`` splits into
        ``R`` blocks along ``split_axis``, block ``j`` travels to rank
        ``j``, received blocks concatenate along ``concat_axis`` in source
        order.  With ``cfg.channels >= 2`` the payload is striped along its
        last dimension into that many independent rails.
        """
        axis = self._a2a_axis()
        if self.axis_sizes[0] == 1:
            return x                   # single rank: nothing moves (and the
                                       # axis may not even be bound here)
        rails = self.a2a_rails(x.shape)
        if rails <= 1:
            return self.transport.all_to_all(
                x, axis, split_axis=split_axis, concat_axis=concat_axis)
        w = x.shape[-1] // rails
        outs = []
        for c in range(rails):
            part = lax.slice_in_dim(x, c * w, (c + 1) * w, axis=x.ndim - 1)
            outs.append(self.transport.all_to_all(
                part, axis, split_axis=split_axis, concat_axis=concat_axis))
        return jnp.concatenate(outs, axis=-1)

    def all_to_all_ragged(self, payload: jax.Array, counts: jax.Array, *,
                          split_axis: int, concat_axis: int
                          ) -> tuple[jax.Array, jax.Array]:
        """All-to-all of capacity-padded blocks plus their valid-row counts.

        The capacity-factor overflow story: each of the ``R`` destination
        blocks along ``split_axis`` is padded to the static capacity, and
        ``counts`` (int32, shape ``(R,)``) carries how many leading rows of
        each block are real.  Both travel; the receiver gets
        ``(recv_payload, recv_counts)`` where ``recv_counts[j]`` is how many
        rows source ``j`` actually filled — positions past the count are
        pad and must be masked by the caller.  Priced as the payload
        exchange plus ``4 * R`` count bytes.
        """
        axis = self._a2a_axis()
        r = self.axis_sizes[0]
        if counts.shape[0] != r:
            raise ValueError(
                f"counts must have shape ({r},), got {counts.shape}")
        recv = self.all_to_all(payload, split_axis=split_axis,
                               concat_axis=concat_axis)
        if r == 1:
            return recv, counts.astype(jnp.int32)
        recv_counts = self.transport.all_to_all(
            counts.astype(jnp.int32), axis, split_axis=0, concat_axis=0)
        return recv, recv_counts

    def moe_schedule(self, shape: Sequence[int],
                     dtype=jnp.float32) -> CommSchedule:
        """Issue slots for one EP dispatch + combine round-trip of a local
        capacity buffer of ``shape``: per-rail dispatch slots ready early
        (they overlap the previous layer / router math) and combine slots
        ready late (they overlap the expert GEMMs)."""
        axis = self._a2a_axis()
        r = self.axis_sizes[0]
        n = 1
        for d in shape:
            n *= int(d)
        itemsize = jnp.dtype(dtype).itemsize
        rails = self.a2a_rails(shape)
        phase_bytes = self.transport.predicted_a2a_bytes_per_device(
            n, r, itemsize)
        return build_moe_schedule(phase_bytes, rails)

    def a2a_plan(self, shape: Sequence[int], dtype=jnp.float32) -> A2APlan:
        """Predicted wire cost of one EP dispatch + combine round-trip —
        the :class:`~repro.comm.plan.A2APlan` analogue of :meth:`plan`,
        read by the dry-run's moe suite and ``benchmarks/bench_moe.py``."""
        axis = self._a2a_axis()
        r = self.axis_sizes[0]
        n = 1
        for d in shape:
            n *= int(d)
        itemsize = jnp.dtype(dtype).itemsize
        rails = self.a2a_rails(shape)
        sched = self.moe_schedule(shape, dtype)
        by_channel: dict[int, list[int]] = {}
        for slot in sched.slots:
            by_channel.setdefault(slot.channel, []).extend(slot.bucket_ids)
        chans = tuple(HaloChannel(c, tuple(sorted(u)), sum(
            sched.bucket_sizes[i] for i in u)) for c, u in
            sorted(by_channel.items()))
        keys = tuple(f"{phase}#{c}" for phase in ("dispatch", "combine")
                     for c in range(rails))
        return A2APlan(
            transport=self.cfg.transport,
            axis=axis,
            axis_size=r,
            elems_per_device=n,
            itemsize=itemsize,
            unit_keys=keys,
            unit_bytes=sched.bucket_sizes,
            messages_per_unit=self.transport.predicted_a2a_messages_per_device(r),
            channels=chans,
            overlap_fraction=sched.overlap_fraction,
        )

    # -- tree-level ops (inside a fully-manual shard_map) --------------------

    def _mean_buckets(self, buckets: list) -> list:
        if not self.cfg.mean:
            return buckets
        inv = jnp.asarray(1.0 / self.world, jnp.float32)
        return [b * inv for b in buckets]

    def _mean_tree(self, tree):
        if not self.cfg.mean:
            return tree
        inv = 1.0 / self.world
        return jax.tree.map(
            lambda x: (x.astype(jnp.float32) * inv).astype(x.dtype), tree)

    def all_reduce_tree(self, grads, ef_state=None):
        """All-reduce(-mean) a local gradient pytree.  Returns
        ``(reduced, new_ef_state)``; ``ef_state`` passes through as ``None``
        unless the transport carries a lossy codec."""
        if not self.axes:
            return grads, ef_state
        if not self.cfg.fuse:
            red = jax.tree.map(lambda x: self.transport.all_reduce(x), grads)
            return self._mean_tree(red), ef_state
        buckets, bplan = self.bucketer.bucketize(grads)
        new_res = ef_state
        if self._ef is not None and ef_state is not None:
            buckets, new_res = self._ef.compensate(buckets, list(ef_state))
        reduced = self._mean_buckets(self.all_reduce(buckets))
        return self.bucketer.debucketize(reduced, bplan), new_res

    def reduce_scatter_tree(self, grads):
        """Reduce-scatter(-mean) into flat bucket shards (ZeRO path).
        Returns ``(shards, bucket_plan)``; invert with
        :meth:`all_gather_buckets`."""
        buckets, bplan = self.bucketer.bucketize(grads)
        inv = jnp.asarray(1.0 / self.world if self.cfg.mean else 1.0,
                          jnp.float32)
        shards = [s * inv for s in self.reduce_scatter(buckets)]
        return shards, bplan

    def all_gather_buckets(self, shards: list, bplan: BucketPlan | None = None):
        """Inverse of :meth:`reduce_scatter_tree`: full buckets, or the
        debucketized tree when ``bplan`` is given."""
        full = self.all_gather(shards)
        return full if bplan is None else self.bucketer.debucketize(full, bplan)

    # -- dependency-aware scheduled reduction --------------------------------

    def schedule(self, tree, policy: str, microbatches: int = 1
                 ) -> CommSchedule:
        """The :class:`~repro.comm.schedule.CommSchedule` this communicator
        would execute for one gradient-shaped pytree: bucket layout from the
        bucketer, striping from ``cfg.channels``, issue order from
        ``policy``."""
        if not self.cfg.fuse:
            # per-tensor collectives: every leaf is its own "bucket"
            sizes = [int(np.prod(l.shape)) if l.shape else 1
                     for l in jax.tree.leaves(tree)]
            return build_schedule(policy, sizes, microbatches=microbatches,
                                  channels=self.cfg.channels)
        bplan = self.bucketer.plan(tree)
        return build_schedule(policy, bplan.bucket_sizes,
                              microbatches=microbatches,
                              channels=self.cfg.channels)

    def arena_schedule(self, tree, policy: str, microbatches: int = 1
                       ) -> CommSchedule:
        """The span-level schedule the arena mode executes: the bucket
        schedule of :meth:`schedule` with each channel's contiguous arena
        span fused into a single issue
        (:func:`repro.mem.layout.fuse_schedule`)."""
        from repro.mem.layout import fuse_schedule

        return fuse_schedule(self.schedule(tree, policy, microbatches),
                             self.arena_layout(tree))

    def reduce_scheduled(self, grad_fn, params, batch,
                         schedule: CommSchedule, *, op: str = "all_reduce",
                         arena: "CommArena | QuantCommArena | None" = None,
                         arena_buf: jax.Array | None = None,
                         ef_buf: jax.Array | None = None):
        """Run ``grad_fn(params, microbatch) -> (loss, grads)`` over
        ``schedule.microbatches`` slices of ``batch`` (split on the leading
        axis), issuing each gradient bucket's collective at its schedule
        slot.  Runs *inside* a fully-manual ``shard_map``.

        ``op`` selects the per-bucket collective:

        * ``"all_reduce"``     -> returns ``(mean_loss, reduced_tree)``;
        * ``"reduce_scatter"`` -> ``(mean_loss, (shards, bucket_plan))`` —
          each microbatch's buckets reduce-scatter as they are produced
          (streamed ZeRO), shards accumulate locally;
        * ``"none"``           -> ``(mean_loss, accumulated_tree)`` for
          modes whose reduction rides the autodiff transpose (FSDP); the
          schedule then only describes the intrinsic overlap.

        Buckets sharing a rail (``schedule.channels >= 1``) are chained with
        :func:`~repro.core.topology.order_token` so each rail issues FIFO in
        readiness order; rails stay independent.  ``channels == 0`` leaves
        every collective unconstrained.

        **Arena mode** (``arena`` given): gradients pack into the
        page-aligned :class:`~repro.mem.arena.CommArena` buffer and each
        issue slot reduces one contiguous arena *span* instead of a bucket
        — fewer, larger, aligned messages (``schedule`` must then be the
        span-level :meth:`arena_schedule`).  ``arena_buf`` is the persistent
        (donated) buffer from the step state; it is returned alongside the
        result so the caller can thread it back:

        * ``"all_reduce"``     -> ``(loss, (tree, arena_out))``;
        * ``"reduce_scatter"`` -> ``(loss, (span_shards, bucket_plan,
          arena_out))`` — invert with :meth:`all_gather` over the spans and
          :meth:`CommArena.unpack_spans <repro.mem.arena.CommArena
          .unpack_spans>`;
        * ``"none"``           -> ``(loss, (tree, arena_out))`` — the arena
          is the microbatch accumulation buffer (FSDP: reduction rides the
          gather transpose, so only residency changes).

        **Quantized arena mode** (``arena`` a
        :class:`~repro.mem.arena.QuantCommArena`): packing *encodes* (fused
        pack+quantize with the ``ef_buf`` error-feedback accumulator
        compensated at pack time), spans are decoded to fp32 before the
        collective (codec-capable transports re-encode on every hop, so
        the wire carries int8 + scales; others reduce fp32), and the
        reduced values re-encode into the arena for the fused
        dequant+unpack out.  Every return gains the threaded-back ``ef``:
        ``(loss, (tree, arena_out, ef_out))`` for ``all_reduce``/``none``,
        ``(loss, (span_shards, bucket_plan, arena_out, ef_out))`` for
        ``reduce_scatter``.
        """
        if op not in ("all_reduce", "reduce_scatter", "none"):
            raise ValueError(f"op must be all_reduce|reduce_scatter|none, "
                             f"got {op!r}")
        if op == "reduce_scatter" and not self.spec.supports_rs:
            raise ValueError(
                f"transport {self.cfg.transport!r} does not support "
                f"reduce-scatter (supports_rs=False)")
        if arena is not None:
            from repro.mem.arena import QuantCommArena

            if isinstance(arena, QuantCommArena):
                return self._reduce_scheduled_arena_quant(
                    grad_fn, params, batch, schedule, op, arena, arena_buf,
                    ef_buf)
            return self._reduce_scheduled_arena(grad_fn, params, batch,
                                                schedule, op, arena,
                                                arena_buf)
        if not self.axes:
            if op == "reduce_scatter":
                # downgrading would change the return shape from
                # (shards, plan) to a tree under the caller's feet
                raise ValueError(
                    "reduce_scatter schedule needs data axes; this "
                    "communicator's mesh has none")
            op = "none"                      # no data axes: nothing to reduce
        m = max(schedule.microbatches, 1)
        collective = (self.transport.all_reduce if op == "all_reduce"
                      else self.transport.reduce_scatter)

        micro = (jax.tree.map(
            lambda x: x.reshape((m, x.shape[0] // m) + x.shape[1:]), batch)
            if m > 1 else None)
        inv = 1.0 / m
        deps: dict[int, jax.Array] = {}      # rail -> FIFO ordering token
        chained = schedule.channels >= 1

        def issue(bucket, channel):
            if not chained:
                return collective(bucket)
            y = collective(order_token(deps.get(channel), bucket))
            deps[channel] = y.reshape(-1)[0]
            return y

        streamed = schedule.policy != "accumulate_then_reduce"
        fused = self.cfg.fuse
        losses = []
        acc = None                           # tree (op=none) or bucket list
        bplan: BucketPlan | None = None
        treedef = None                       # unfused (per-tensor) layout
        for i in range(m):
            mb = batch if m == 1 else jax.tree.map(lambda x: x[i], micro)
            loss, grads = grad_fn(params, mb)
            losses.append(loss)
            if op == "none":
                if m > 1:
                    grads = jax.tree.map(
                        lambda g: g.astype(jnp.float32) * inv, grads)
                acc = (grads if acc is None
                       else jax.tree.map(jnp.add, acc, grads))
                continue
            if fused:
                buckets, bplan = self.bucketer.bucketize(grads)
                n_units = bplan.n_buckets
            else:                            # per-tensor: leaf == "bucket"
                buckets, treedef = jax.tree.flatten(grads)
                n_units = len(buckets)
            if n_units != schedule.n_buckets:
                raise ValueError(
                    f"schedule has {schedule.n_buckets} buckets but the "
                    f"gradient tree bucketizes into {n_units}; build "
                    f"the schedule with Communicator.schedule on the same "
                    f"tree")
            if m > 1:
                buckets = [b.astype(jnp.float32) * inv for b in buckets]
            if streamed:
                out: list = [None] * len(buckets)
                for slot in schedule.slots_for_phase(i):
                    for b in slot.bucket_ids:
                        out[b] = issue(buckets[b], slot.channel)
                acc = out if acc is None else [a + o for a, o in zip(acc, out)]
            else:
                acc = (buckets if acc is None
                       else [a + b for a, b in zip(acc, buckets)])
        if op != "none" and not streamed:
            out = [None] * len(acc)
            for slot in schedule.slots_for_phase(m - 1):
                for b in slot.bucket_ids:
                    out[b] = issue(acc[b], slot.channel)
            acc = out
        loss = losses[0] if m == 1 else jnp.mean(jnp.stack(losses))
        if op == "none":
            return loss, acc
        if not fused:                        # per-tensor mean, dtype-stable
            if self.cfg.mean:
                winv = 1.0 / self.world
                acc = [(a.astype(jnp.float32) * winv).astype(a.dtype)
                       for a in acc]
            return loss, jax.tree.unflatten(treedef, acc)
        acc = self._mean_buckets(acc)
        if op == "reduce_scatter":
            return loss, (acc, bplan)
        return loss, self.bucketer.debucketize(acc, bplan)

    def _reduce_scheduled_arena(self, grad_fn, params, batch,
                                schedule: CommSchedule, op: str,
                                arena: "CommArena",
                                arena_buf: jax.Array | None):
        """Arena-mode body of :meth:`reduce_scheduled` (see there).  Every
        collective moves one contiguous page-quantized span of the arena —
        padding crosses the wire, buckets never move individually."""
        layout = arena.layout
        if not self.axes:
            raise ValueError("arena mode needs data axes; this "
                             "communicator's mesh has none")
        if op != "none":
            if not self.cfg.fuse:
                raise ValueError("arena mode needs fused aligned buckets "
                                 "(fuse=True)")
            if schedule.n_buckets != layout.n_spans:
                raise ValueError(
                    f"arena mode expects a span-level schedule with "
                    f"{layout.n_spans} spans, got {schedule.n_buckets}; "
                    f"build it with Communicator.arena_schedule")
        m = max(schedule.microbatches, 1)
        collective = (self.transport.all_reduce if op == "all_reduce"
                      else self.transport.reduce_scatter)
        micro = (jax.tree.map(
            lambda x: x.reshape((m, x.shape[0] // m) + x.shape[1:]), batch)
            if m > 1 else None)
        inv = 1.0 / m
        deps: dict[int, jax.Array] = {}
        chained = schedule.channels >= 1

        def issue(span_buf, channel):
            if not chained:
                return collective(span_buf)
            y = collective(order_token(deps.get(channel), span_buf))
            deps[channel] = y.reshape(-1)[0]
            return y

        def reduce_spans(buf, phase):
            """All-reduce each span in place (slice, reduce, write back)."""
            for slot in schedule.slots_for_phase(phase):
                for s in slot.bucket_ids:       # span indices
                    sp = layout.spans[s]
                    seg = lax.slice_in_dim(buf, sp.offset,
                                           sp.offset + sp.size, axis=0)
                    buf = lax.dynamic_update_slice_in_dim(
                        buf, issue(seg, slot.channel), sp.offset, axis=0)
            return buf

        def scatter_spans(buf, phase, out):
            """Reduce-scatter each span into its shard slot."""
            for slot in schedule.slots_for_phase(phase):
                for s in slot.bucket_ids:
                    sp = layout.spans[s]
                    seg = lax.slice_in_dim(buf, sp.offset,
                                           sp.offset + sp.size, axis=0)
                    out[s] = issue(seg, slot.channel)
            return out

        streamed = schedule.policy != "accumulate_then_reduce"
        losses = []
        acc = None                 # arena buffer, or span-shard list (RS)
        bplan: BucketPlan | None = None
        treedef = None             # op == "none": the grads tree layout
        leaf_meta: list[tuple] = []
        buf = arena_buf if arena_buf is not None else arena.zeros()
        for i in range(m):
            mb = batch if m == 1 else jax.tree.map(lambda x: x[i], micro)
            loss, grads = grad_fn(params, mb)
            losses.append(loss)
            if op == "none":
                leaves, treedef = jax.tree.flatten(grads)
                if len(leaves) != layout.n_segments:
                    raise ValueError(
                        f"arena has {layout.n_segments} segments but the "
                        f"gradient tree has {len(leaves)} leaves; build "
                        f"the arena from the same tree")
                leaf_meta = [(l.shape, l.dtype) for l in leaves]
                if m > 1:
                    leaves = [l.astype(jnp.float32) * inv for l in leaves]
                buf = arena.pack_into(buf, [l.reshape(-1) for l in leaves])
                acc = buf if acc is None else acc + buf
                continue
            buckets, bplan = self.bucketer.bucketize(grads)
            if bplan.n_buckets != layout.n_segments:
                raise ValueError(
                    f"arena has {layout.n_segments} segments but the "
                    f"gradient tree bucketizes into {bplan.n_buckets}; "
                    f"build the arena with Communicator.arena on the same "
                    f"tree")
            if m > 1:
                buckets = [b.astype(jnp.float32) * inv for b in buckets]
            buf = arena.pack_into(buf, buckets)
            if not streamed:
                acc = buf if acc is None else acc + buf
            elif op == "all_reduce":
                red = reduce_spans(buf, i)
                acc = red if acc is None else acc + red
            else:
                out = scatter_spans(buf, i, [None] * layout.n_spans)
                acc = out if acc is None else [a + o
                                               for a, o in zip(acc, out)]
        if op != "none" and not streamed:
            acc = (reduce_spans(acc, m - 1) if op == "all_reduce"
                   else scatter_spans(acc, m - 1, [None] * layout.n_spans))
        loss = losses[0] if m == 1 else jnp.mean(jnp.stack(losses))
        if op == "none":
            leaves = arena.unpack(acc)
            leaves = [u.reshape(shape).astype(jnp.float32 if m > 1
                                              else dtype)
                      for u, (shape, dtype) in zip(leaves, leaf_meta)]
            return loss, (jax.tree.unflatten(treedef, leaves), acc)
        if op == "reduce_scatter":
            inv_w = jnp.asarray(1.0 / self.world if self.cfg.mean else 1.0,
                                jnp.float32)
            return loss, ([s * inv_w for s in acc], bplan, buf)
        if self.cfg.mean:
            acc = acc * jnp.asarray(1.0 / self.world, jnp.float32)
        tree = self.bucketer.debucketize(arena.unpack(acc), bplan)
        return loss, (tree, acc)

    def _reduce_scheduled_arena_quant(self, grad_fn, params, batch,
                                      schedule: CommSchedule, op: str,
                                      arena: "QuantCommArena",
                                      arena_buf: jax.Array | None,
                                      ef_buf: jax.Array | None):
        """Quantized-arena body of :meth:`reduce_scheduled` (see there).

        The int8 arena cannot accumulate across microbatches, so gradients
        accumulate in fp32 (bucket lists, or reduced span values under the
        streamed policy) and the arena encodes at issue boundaries: fused
        pack+quantize on the way in (error feedback compensated from
        ``ef_buf``, residual written back), span dequant before each
        collective, and — for ``all_reduce`` — a final re-encode of the
        reduced mean so the gradient the caller sees comes out of the fused
        dequant+unpack, exactly what the next step's wire would carry.
        """
        layout = arena.layout
        if not self.axes:
            raise ValueError("arena mode needs data axes; this "
                             "communicator's mesh has none")
        if op != "none":
            if not self.cfg.fuse:
                raise ValueError("arena mode needs fused aligned buckets "
                                 "(fuse=True)")
            if schedule.n_buckets != layout.n_spans:
                raise ValueError(
                    f"arena mode expects a span-level schedule with "
                    f"{layout.n_spans} spans, got {schedule.n_buckets}; "
                    f"build it with Communicator.arena_schedule")
        m = max(schedule.microbatches, 1)
        collective = (self.transport.all_reduce if op == "all_reduce"
                      else self.transport.reduce_scatter)
        micro = (jax.tree.map(
            lambda x: x.reshape((m, x.shape[0] // m) + x.shape[1:]), batch)
            if m > 1 else None)
        inv = 1.0 / m
        deps: dict[int, jax.Array] = {}
        chained = schedule.channels >= 1

        def issue(span_vals, channel):
            if not chained:
                return collective(span_vals)
            y = collective(order_token(deps.get(channel), span_vals))
            deps[channel] = y.reshape(-1)[0]
            return y

        buf = arena_buf if arena_buf is not None else arena.zeros()
        ef = ef_buf
        streamed = schedule.policy != "accumulate_then_reduce"
        losses = []
        span_acc: list | None = None   # fp32 reduced spans (AR) / shards (RS)
        bucket_acc: list | None = None  # accumulate_then_reduce fp32 buckets
        leaf_acc: list | None = None    # op == "none" fp32 leaves
        bplan: BucketPlan | None = None
        treedef = None
        leaf_meta: list[tuple] = []

        def run_phase(phase):
            """Decode each of the phase's spans and issue its collective."""
            nonlocal buf
            out: list = [None] * layout.n_spans
            for slot in schedule.slots_for_phase(phase):
                for s in slot.bucket_ids:       # span indices
                    out[s] = issue(arena.dequant_span(buf, s), slot.channel)
            return out

        for i in range(m):
            mb = batch if m == 1 else jax.tree.map(lambda x: x[i], micro)
            loss, grads = grad_fn(params, mb)
            losses.append(loss)
            if op == "none":
                leaves, treedef = jax.tree.flatten(grads)
                if len(leaves) != layout.n_segments:
                    raise ValueError(
                        f"arena has {layout.n_segments} segments but the "
                        f"gradient tree has {len(leaves)} leaves; build "
                        f"the arena from the same tree")
                leaf_meta = [(l.shape, l.dtype) for l in leaves]
                flat = [l.reshape(-1).astype(jnp.float32) for l in leaves]
                if m > 1:
                    flat = [l * inv for l in flat]
                leaf_acc = (flat if leaf_acc is None
                            else [a + l for a, l in zip(leaf_acc, flat)])
                continue
            buckets, bplan = self.bucketer.bucketize(grads)
            if bplan.n_buckets != layout.n_segments:
                raise ValueError(
                    f"arena has {layout.n_segments} segments but the "
                    f"gradient tree bucketizes into {bplan.n_buckets}; "
                    f"build the arena with Communicator.arena on the same "
                    f"tree")
            buckets = [b.astype(jnp.float32) for b in buckets]
            if m > 1:
                buckets = [b * inv for b in buckets]
            if not streamed:
                bucket_acc = (buckets if bucket_acc is None
                              else [a + b
                                    for a, b in zip(bucket_acc, buckets)])
                continue
            buf, ef = arena.pack_into(buf, buckets, ef)
            out = run_phase(i)
            span_acc = (out if span_acc is None
                        else [a + o for a, o in zip(span_acc, out)])
        if op != "none" and not streamed:
            buf, ef = arena.pack_into(buf, bucket_acc, ef)
            span_acc = run_phase(m - 1)
        loss = losses[0] if m == 1 else jnp.mean(jnp.stack(losses))
        if op == "none":
            buf, ef = arena.pack_into(buf, leaf_acc, ef)
            leaves = arena.unpack(buf)
            leaves = [u.reshape(shape).astype(jnp.float32 if m > 1
                                              else dtype)
                      for u, (shape, dtype) in zip(leaves, leaf_meta)]
            return loss, (jax.tree.unflatten(treedef, leaves), buf, ef)
        if op == "reduce_scatter":
            inv_w = jnp.asarray(1.0 / self.world if self.cfg.mean else 1.0,
                                jnp.float32)
            return loss, ([s * inv_w for s in span_acc], bplan, buf, ef)
        if self.cfg.mean:
            inv_w = jnp.asarray(1.0 / self.world, jnp.float32)
            span_acc = [s * inv_w for s in span_acc]
        for s, vals in enumerate(span_acc):
            buf = arena.requant_span(buf, s, vals)
        tree = self.bucketer.debucketize(arena.unpack(buf), bplan)
        return loss, (tree, buf, ef)

    # -- SPMD wrappers (called OUTSIDE shard_map) ----------------------------

    def reduce(self, grads, specs, ef_state=None):
        """Reduce ``grads`` (mean over the data axes) from the SPMD level.

        ``specs``: pytree of ``PartitionSpec`` congruent with ``grads`` (the
        model-sharding of each gradient).  Returns ``(reduced, ef_state)``.
        """
        if not self.axes:
            return grads, ef_state
        ef_spec = P(tuple(self.mesh.axis_names))
        has_ef = self._ef is not None and ef_state is not None
        in_specs = (specs, ef_spec) if has_ef else (specs,)
        out_specs = (specs, ef_spec) if has_ef else (specs,)

        def inner(*args):
            red, new_res = self.all_reduce_tree(
                args[0], args[1] if has_ef else None)
            return (red, new_res) if has_ef else (red,)

        args = (grads, ef_state) if has_ef else (grads,)
        out = jax.shard_map(inner, mesh=self.mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=False)(*args)
        return (out[0], out[1]) if has_ef else (out[0], ef_state)

    def init_ef_state(self, grads_like, specs):
        """Zero residual buckets as *global* arrays, one local bucket per
        device (leading dim = all mesh axes); ``grads_like`` may be
        ``ShapeDtypeStruct``s.  ``None`` when the transport is lossless."""
        if self._ef is None:
            return None
        ef_spec = P(tuple(self.mesh.axis_names))

        def inner(g):
            buckets, _ = self.bucketer.bucketize(g)
            return [jnp.zeros_like(b) for b in buckets]

        fn = jax.shard_map(inner, mesh=self.mesh, in_specs=(specs,),
                           out_specs=ef_spec, check_vma=False)
        return jax.jit(fn)(grads_like) if not _is_abstract(grads_like) \
            else jax.eval_shape(fn, grads_like)

    # -- analysis ------------------------------------------------------------

    def predicted_collective_bytes(self, grads_like) -> dict[str, float]:
        """Napkin-math wire bytes per device (reads the :class:`CommPlan`)."""
        return self.plan(grads_like).predicted_collective_bytes()


def _is_abstract(tree) -> bool:
    leaves = jax.tree.leaves(tree)
    return bool(leaves) and isinstance(leaves[0], jax.ShapeDtypeStruct)

"""Transport registry: named collective schedules with declared capabilities.

A *transport* is one way of moving a flat, pre-padded bucket across the data
axes of the mesh — the role the PSM2 endpoint configuration plays in the
paper.  Each transport registers itself under a short name together with a
:class:`TransportSpec` declaring what it can do (``supports_rs`` for the
ZeRO reduce-scatter/all-gather paths, ``supports_codec`` / ``wire_dtypes``
for lossy or narrow wire formats), so an invalid combination fails when the
:class:`~repro.comm.api.Communicator` is constructed — not at trace time
deep inside a jitted step.

Built-in transports (the former ``ReduceConfig.policy`` branches):

========================  ====================================================
``ring``                  flat multi-channel bidirectional ring (pod-oblivious)
``ring_hier``             pod-aware hierarchical ring (RS inner, recurse outer)
``psum``                  XLA's native all-reduce (vendor reference)
``a2a``                   native ``lax.all_to_all`` (EP dispatch/combine)
========================  ====================================================

(The old ``ring_compressed`` shim was removed: use any ring transport with
``CommConfig(wire_codec="int8")`` — see :mod:`repro.comm.wire_codec`.)

``supports_a2a`` marks transports that can move an expert-parallel capacity
buffer: ring transports implement it as ``p - 1`` explicit pairwise ppermute
hops, ``psum`` as the honest replicated fallback (scatter into the full
exchange matrix, all-reduce, slice own column — priced at its true
``2(p-1)`` cost), and ``a2a`` lowers to a single HLO ``all-to-all`` op.

Third-party schedules register the same way::

    @register_transport("my_ring", supports_rs=True)
    class MyRing(RingTransport):
        ...
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Sequence, Type

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import ring as ring_lib
from repro.core.ring import RingConfig

WIRE_DTYPES_ANY = (None, "bfloat16", "float16", "float32")


@dataclass(frozen=True)
class TransportSpec:
    """Construction-time capability declaration of one transport."""

    name: str
    supports_rs: bool                      # reduce_scatter / all_gather pairs
    supports_codec: bool                   # lossy block codec on the wire
    wire_dtypes: tuple[str | None, ...]    # allowed narrow wire dtypes
    codec: str | None                      # codec this transport always uses
    hierarchical: bool                     # pod-aware byte accounting
    supports_a2a: bool                     # all_to_all (EP dispatch/combine)
    description: str


_TRANSPORTS: dict[str, tuple[TransportSpec, Type["Transport"]]] = {}


def register_transport(name: str, *, supports_rs: bool,
                       supports_codec: bool = False,
                       wire_dtypes: tuple[str | None, ...] = WIRE_DTYPES_ANY,
                       codec: str | None = None,
                       hierarchical: bool = False,
                       supports_a2a: bool = False,
                       description: str = "") -> Callable[[type], type]:
    """Class decorator registering a :class:`Transport` under ``name``."""

    def deco(cls: type) -> type:
        if name in _TRANSPORTS:
            raise ValueError(f"transport {name!r} already registered")
        spec = TransportSpec(name=name, supports_rs=supports_rs,
                             supports_codec=supports_codec,
                             wire_dtypes=wire_dtypes, codec=codec,
                             hierarchical=hierarchical,
                             supports_a2a=supports_a2a,
                             description=description or (cls.__doc__ or "").strip())
        _TRANSPORTS[name] = (spec, cls)
        cls.spec = spec
        return cls

    return deco


def get_transport(name: str) -> tuple[TransportSpec, Type["Transport"]]:
    """Lookup; raises with the full menu on an unknown name."""
    try:
        return _TRANSPORTS[name]
    except KeyError:
        if name == "ring_compressed":
            raise ValueError(
                "transport 'ring_compressed' was removed; use a ring "
                "transport with CommConfig(wire_codec='int8') instead "
                "(codecs live in repro.comm.wire_codec)") from None
        raise ValueError(
            f"unknown transport {name!r}; registered transports: "
            f"{tuple(sorted(_TRANSPORTS))}") from None


def list_transports() -> tuple[str, ...]:
    return tuple(sorted(_TRANSPORTS))


def transport_specs() -> dict[str, TransportSpec]:
    return {name: spec for name, (spec, _) in _TRANSPORTS.items()}


# ---------------------------------------------------------------------------
# transport implementations
# ---------------------------------------------------------------------------


class Transport:
    """One collective schedule over the data axes.

    All methods run *inside* a fully-manual ``shard_map`` on flat 1-D buffers
    already padded to :meth:`flat_divisor` (``core.bucketing`` guarantees
    that).  ``axes`` is mesh-ordered (outermost first, e.g. ``("pod",
    "data")``); schedules that care about pod locality reverse it themselves.
    """

    spec: TransportSpec  # filled in by @register_transport

    def __init__(self, axes: Sequence[str], ring_cfg: RingConfig):
        self.axes = tuple(axes)
        self.ring_cfg = ring_cfg

    # inner (fastest / intra-pod) axis first — RS ownership order
    @property
    def ordered_axes(self) -> tuple[str, ...]:
        return tuple(reversed(self.axes))

    def flat_divisor(self, axis_sizes: Sequence[int]) -> int:
        return self.ring_cfg.flat_divisor(axis_sizes)

    # -- collectives --------------------------------------------------------

    def all_reduce(self, flat: jax.Array) -> jax.Array:
        raise NotImplementedError

    def reduce_scatter(self, flat: jax.Array) -> jax.Array:
        raise NotImplementedError(
            f"transport {self.spec.name!r} does not support reduce-scatter")

    def all_gather(self, shard: jax.Array) -> jax.Array:
        raise NotImplementedError(
            f"transport {self.spec.name!r} does not support all-gather")

    def all_to_all(self, x: jax.Array, axis: str, *, split_axis: int,
                   concat_axis: int) -> jax.Array:
        """Tiled all-to-all over a single mesh axis (EP dispatch/combine)."""
        raise NotImplementedError(
            f"transport {self.spec.name!r} does not support all-to-all")

    # -- analysis -----------------------------------------------------------

    def predicted_bytes_per_device(self, n_elems: int,
                                   axis_sizes: Sequence[int]) -> float:
        """Napkin-math wire bytes per device for one all-reduce of
        ``n_elems`` elements (§Perf hypothesis logs / dry-run report)."""
        codec = self.ring_cfg.make_codec()
        wire_per_elem = codec.wire_bytes(max(n_elems, 1)) / max(n_elems, 1)
        if self.spec.hierarchical and len(axis_sizes) > 0:
            inner_p = axis_sizes[-1]
            world = 1
            for p in axis_sizes:
                world *= p
            outer = world // max(inner_p, 1)
            inner_bytes = 2 * (inner_p - 1) / max(inner_p, 1) * n_elems * wire_per_elem
            outer_bytes = (2 * (outer - 1) / outer * (n_elems / inner_p)
                           * wire_per_elem if outer > 1 else 0.0)
            return inner_bytes + outer_bytes
        total = 0.0
        for p in axis_sizes:
            total += 2 * (p - 1) / max(p, 1) * n_elems * wire_per_elem
        return total

    def predicted_messages_per_device(self, axis_sizes: Sequence[int]
                                      ) -> float:
        """Discrete sends per device for one all-reduce of one bucket —
        the α term of :class:`repro.comm.plan.LatencyModel`.  Baseline: a
        single ring per axis pays ``(p−1)`` reduce-scatter plus ``(p−1)``
        all-gather hops; explicit ring transports multiply by their
        chunk × direction parallel chains (more, smaller messages — same
        bytes), see :class:`RingTransport`."""
        return float(sum(2 * (p - 1) for p in axis_sizes))

    def predicted_a2a_bytes_per_device(self, n_elems: int, axis_size: int,
                                       itemsize: int = 4) -> float:
        """Wire bytes per device for one all-to-all of a local ``n_elems``
        payload: ``(p-1)/p`` of it leaves the device (the own-block stays)."""
        p = max(int(axis_size), 1)
        return (p - 1) / p * n_elems * itemsize

    def predicted_a2a_messages_per_device(self, axis_size: int) -> float:
        """Sends per device for one all-to-all: ``p - 1`` pairwise hops."""
        return float(max(int(axis_size) - 1, 0))


@register_transport(
    "ring", supports_rs=True, supports_codec=True, supports_a2a=True,
    description="flat multi-channel bidirectional ppermute ring; every byte "
                "crosses every axis at full size (pod-oblivious baseline)")
class RingTransport(Transport):
    """Flat ring: full-size ring all-reduce per data axis in turn."""

    def all_reduce(self, flat: jax.Array) -> jax.Array:
        return ring_lib.flat_all_reduce(flat, self.axes, self.ring_cfg)

    def all_to_all(self, x: jax.Array, axis: str, *, split_axis: int,
                   concat_axis: int) -> jax.Array:
        return ring_lib.ring_all_to_all(x, axis, split_axis=split_axis,
                                        concat_axis=concat_axis)

    def predicted_messages_per_device(self, axis_sizes: Sequence[int]
                                      ) -> float:
        mult = self.ring_cfg.chunks * (2 if self.ring_cfg.bidirectional
                                       else 1)
        return super().predicted_messages_per_device(axis_sizes) * mult

    def reduce_scatter(self, flat: jax.Array) -> jax.Array:
        for axis in self.ordered_axes:
            flat = ring_lib.ring_reduce_scatter(flat, axis, self.ring_cfg)
        return flat

    def all_gather(self, shard: jax.Array) -> jax.Array:
        for axis in reversed(self.ordered_axes):
            shard = ring_lib.ring_all_gather(shard, axis, self.ring_cfg)
        return shard


@register_transport(
    "ring_hier", supports_rs=True, supports_codec=True, hierarchical=True,
    supports_a2a=True,
    description="pod-aware hierarchical ring: reduce-scatter the intra-pod "
                "axis first so cross-pod bytes shrink by the pod size")
class HierRingTransport(RingTransport):
    """Hierarchical ring (the paper's optimised schedule; default)."""

    def all_reduce(self, flat: jax.Array) -> jax.Array:
        return ring_lib.hierarchical_all_reduce(flat, self.ordered_axes,
                                                self.ring_cfg)


@register_transport(
    "psum", supports_rs=False, wire_dtypes=(None,), supports_a2a=True,
    description="XLA's built-in all-reduce (vendor reference point); "
                "no explicit schedule, no RS/AG decomposition; all_to_all "
                "is the honest replicated fallback (full-matrix psum)")
class PsumTransport(Transport):
    """Native ``lax.psum`` over the data axes."""

    def all_reduce(self, flat: jax.Array) -> jax.Array:
        return lax.psum(flat, self.axes)

    def all_to_all(self, x: jax.Array, axis: str, *, split_axis: int,
                   concat_axis: int) -> jax.Array:
        """Replicated-psum emulation — the pre-a2a MoE dispatch pattern.

        Each rank scatters its row of the (src, dst) exchange matrix into a
        zero-padded full buffer, all-reduces the whole matrix, then slices
        its own column.  Every byte of the matrix crosses the wire (the
        ``2(p-1)`` replicated tax this PR's ring/native paths eliminate);
        kept as the honest fallback so the A/B cost is measurable.
        """
        p = lax.axis_size(axis)
        if p == 1:
            return x
        n = x.shape[split_axis]
        if n % p != 0:
            raise ValueError(
                f"all_to_all split dim {n} not divisible by axis size {p}")
        blk = n // p
        blocks = jnp.stack(
            [lax.slice_in_dim(x, j * blk, (j + 1) * blk, axis=split_axis)
             for j in range(p)], axis=0)                  # (p_dst, ...)
        i = lax.axis_index(axis)
        full = jnp.zeros((p,) + blocks.shape, blocks.dtype)
        full = lax.dynamic_update_slice_in_dim(full, blocks[None], i, axis=0)
        full = lax.psum(full, axis)                       # (p_src, p_dst, ...)
        col = lax.dynamic_index_in_dim(full, i, axis=1, keepdims=False)
        return jnp.concatenate([col[j] for j in range(p)], axis=concat_axis)

    def predicted_bytes_per_device(self, n_elems: int,
                                   axis_sizes: Sequence[int]) -> float:
        # assume the vendor collective is also a bandwidth-optimal ring
        return super().predicted_bytes_per_device(n_elems, axis_sizes)

    def predicted_messages_per_device(self, axis_sizes: Sequence[int]
                                      ) -> float:
        # one fused op over the joint group: a ring-equivalent hop count
        # over the whole world, not one ring per axis
        world = 1
        for p in axis_sizes:
            world *= p
        return float(2 * (world - 1)) if world > 1 else 0.0

    def predicted_a2a_bytes_per_device(self, n_elems: int, axis_size: int,
                                       itemsize: int = 4) -> float:
        # honest replicated cost: the full (p, n) exchange matrix is
        # all-reduced, 2(p-1)/p of p*n elems per device
        p = max(int(axis_size), 1)
        return 2 * (p - 1) * n_elems * itemsize

    def predicted_a2a_messages_per_device(self, axis_size: int) -> float:
        p = max(int(axis_size), 1)
        return float(2 * (p - 1))


@register_transport(
    "a2a", supports_rs=False, wire_dtypes=(None,), supports_a2a=True,
    description="native lax.all_to_all (single HLO all-to-all op per "
                "exchange); all_reduce delegates to psum")
class NativeA2ATransport(Transport):
    """Native ``lax.all_to_all`` — the vendor collective for EP dispatch."""

    def all_reduce(self, flat: jax.Array) -> jax.Array:
        return lax.psum(flat, self.axes)

    def all_to_all(self, x: jax.Array, axis: str, *, split_axis: int,
                   concat_axis: int) -> jax.Array:
        if lax.axis_size(axis) == 1:
            return x
        return lax.all_to_all(x, axis, split_axis, concat_axis, tiled=True)

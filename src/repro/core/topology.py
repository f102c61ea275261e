"""Ring/mesh topology helpers for explicit collective schedules.

The paper drives a fixed set of point-to-point channels (8 comm threads, one
per direction / chunk) through the fabric.  On TPU the analogous schedule is a
set of ``lax.ppermute`` chains over named mesh axes; this module centralises
the permutation tables and axis bookkeeping so every collective in
``core.ring`` / ``core.halo`` draws from one audited source.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Sequence

import jax


Axis = str


def ring_perm(size: int, direction: int = +1) -> list[tuple[int, int]]:
    """Permutation table sending rank ``i`` -> ``i + direction (mod size)``."""
    if direction not in (+1, -1):
        raise ValueError(f"ring direction must be +-1, got {direction}")
    return [(i, (i + direction) % size) for i in range(size)]


def mesh_axis_size(mesh: jax.sharding.Mesh, axis: Axis) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape))[axis]


@dataclass(frozen=True)
class ChannelSpec:
    """One concurrent communication channel (paper: one comm thread/endpoint).

    ``direction`` is the ring orientation; ``chunk`` indexes the payload slice
    this channel carries.  A schedule with ``2 * n_chunks`` channels is the
    bidirectional, chunked configuration that mirrors the paper's eight
    threaded endpoints over dual rails.
    """

    direction: int
    chunk: int


def channel_schedule(n_chunks: int, bidirectional: bool) -> list[ChannelSpec]:
    dirs = (+1, -1) if bidirectional else (+1,)
    return [ChannelSpec(d, c) for c in range(n_chunks) for d in dirs]


def order_token(dep, x):
    """Thread a scalar data dependency into ``x`` so XLA cannot reorder it
    before ``dep`` is available (one rail / one sequential schedule step).
    ``dep is None`` means no constraint.  The zero-multiply keeps the value
    unchanged while making ``x`` data-dependent on ``dep``."""
    import jax.numpy as jnp

    if dep is None:
        return x
    return x + jnp.zeros((), x.dtype) * dep.astype(x.dtype)


def padded_size(n: int, multiple: int) -> int:
    """Smallest ``m >= n`` with ``m % multiple == 0`` (lane/ring alignment)."""
    if multiple <= 0:
        raise ValueError(f"multiple must be positive, got {multiple}")
    return int(math.ceil(n / multiple) * multiple)


def reduce_axes_of(mesh_axis_names: Sequence[Axis], data_axes: Sequence[Axis]) -> tuple[Axis, ...]:
    """The subset of ``data_axes`` actually present on the mesh, mesh-ordered."""
    present = [a for a in mesh_axis_names if a in set(data_axes)]
    return tuple(present)

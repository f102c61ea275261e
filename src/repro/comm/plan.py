"""CommPlan: one object describing how a gradient pytree moves.

Fuses the three views that used to live in three places:

* the :class:`~repro.core.bucketing.BucketPlan` (which leaf lands where in
  which fused buffer — the paper's guaranteed-large-buffer layout);
* the **channel assignment** (which bucket rides which virtual channel —
  the paper's multi-rail PSM2 endpoints as a config knob);
* the **predicted wire bytes** (the napkin-math roofline term that
  ``GradientReducer.predicted_collective_bytes`` used to compute).

Benchmarks and the dry-run report read this one object.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Sequence

from repro.core.bucketing import BucketPlan

# Per-message launch + small-message latency cost, seconds.  The paper's
# core observation is that once the wire runs near line rate, per-message
# overhead — not bandwidth — dominates small collectives; 1.5 µs is the
# order of an Omni-Path/ICI small-message one-way latency and makes the
# α term visible exactly where the paper says it matters (CG inner
# products, tiny gradient buckets) without perturbing bulk-transfer cells.
ALPHA_S = 1.5e-6


@dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peaks the roofline prices a program against."""

    bf16_flops: float        # dense bf16 matmul FLOP/s
    hbm_bandwidth: float     # HBM bytes/s
    link_bandwidth: float    # ICI bytes/s per link, one direction


# Keyed by ``jax.Device.device_kind``.  TPU v5e (device_kind "TPU v5
# lite"): Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 16 GB
# of HBM at 819 GB/s, 1,600 Gbit/s of inter-chip interconnect over 4 links
# (50 GB/s per link).  A device that is not listed has no prices.
DEVICE_PEAKS: dict[str, DevicePeaks] = {
    "TPU v5 lite": DevicePeaks(bf16_flops=197e12, hbm_bandwidth=819e9,
                               link_bandwidth=1600e9 / 8 / 4),
}

# The production target the dry-run's described meshes are priced for.
V5E = DEVICE_PEAKS["TPU v5 lite"]


def device_peaks(device_kind: str) -> DevicePeaks:
    """Peaks of ``device_kind``; raises for a device the table lacks."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(DEVICE_PEAKS)}") from None


# Per-link one-direction bandwidth, bytes/s.  Single source for both β
# terms: :class:`LatencyModel` here and ``repro.launch.roofline.ICI_BW``.
LINK_BANDWIDTH = V5E.link_bandwidth

# Per-chip HBM bandwidth, bytes/s (v5e).  Single source for the roofline
# memory term and the codec kernel-time pricing in
# :meth:`CommPlan.codec_tradeoff` — the fused pack+quantize/dequant passes
# are pure streaming kernels, so their cost is HBM bytes over this number.
HBM_BANDWIDTH = V5E.hbm_bandwidth


@dataclass(frozen=True)
class LatencyModel:
    """α/β cost model of one device's collective traffic:

        t_collective = α · messages + bytes / bandwidth

    ``messages`` counts discrete network operations whose launch latency
    cannot be amortised (ring hops, ``ppermute`` payloads); ``bytes`` is
    the per-device wire-byte total the bandwidth term amortises.  The β
    term alone is what the roofline used before solver variants made the
    message *count* a first-class design axis (2 vs 1 vs 1/s reductions
    per CG iteration)."""

    alpha_s: float = ALPHA_S
    bandwidth: float = LINK_BANDWIDTH

    def collective_seconds(self, messages: float, nbytes: float) -> float:
        return self.alpha_s * float(messages) + float(nbytes) / self.bandwidth

    @classmethod
    def from_record(cls, record) -> "LatencyModel":
        """Measured constants from a tuning-DB record (or a bare fit dict /
        :class:`repro.tune.fit.FitResult`): what ``dryrun --tuned`` prices
        cells with instead of the hardcoded guesses above."""
        if hasattr(record, "alpha_s"):          # FitResult (duck-typed)
            return cls(alpha_s=float(record.alpha_s),
                       bandwidth=float(record.bandwidth))
        fit = record.get("fit", record)         # DB record or raw fit dict
        return cls(alpha_s=float(fit["alpha_s"]),
                   bandwidth=float(fit["bandwidth"]))


@dataclass(frozen=True)
class ChannelAssignment:
    """Buckets carried by one virtual channel (independent collective)."""

    channel: int
    buckets: tuple[int, ...]   # indices into the bucket list, ascending
    elems: int                 # total padded elements on this channel


def assign_channels(bucket_sizes: Sequence[int], channels: int
                    ) -> tuple[ChannelAssignment, ...]:
    """Greedy least-loaded striping of buckets across ``channels`` virtual
    channels.  Deterministic: buckets are visited largest-first, ties broken
    by index, and each lands on the currently lightest channel."""
    n = max(int(channels), 1)
    loads = [0] * n
    members: list[list[int]] = [[] for _ in range(n)]
    order = sorted(range(len(bucket_sizes)),
                   key=lambda i: (-int(bucket_sizes[i]), i))
    for i in order:
        c = min(range(n), key=lambda j: (loads[j], j))
        members[c].append(i)
        loads[c] += int(bucket_sizes[i])
    return tuple(ChannelAssignment(c, tuple(sorted(members[c])), loads[c])
                 for c in range(n))


@dataclass(frozen=True)
class CommPlan:
    """Bucket layout + channel striping + predicted bytes for one pytree."""

    transport: str
    axes: tuple[str, ...]
    axis_sizes: tuple[int, ...]
    bucket_plan: BucketPlan
    channels: tuple[ChannelAssignment, ...]
    wire_bytes_per_elem: float     # codec/wire-dtype bytes per element
    bytes_per_device: float        # predicted all-reduce wire bytes/device
    messages_per_device: float = 0.0  # discrete sends/device (α latency term)
    # arena mode (repro.mem): page-quantized fused-span layout + its cost.
    # The arena byte term covers the page padding too — in arena mode the
    # padding crosses the wire, so the prediction must not pretend otherwise.
    arena_layout: "object | None" = None     # repro.mem.layout.ArenaLayout
    arena_bytes_per_device: float = 0.0      # wire bytes incl. page padding
    arena_messages_per_device: float = 0.0   # α term at one send per span
    # quantized wire (repro.kernels.pack_quant): codec identity, priced so
    # the compressed prediction is checkable against lowered HLO at 0
    # tolerance (mem-suite codec cells) and against the fp32 twin.
    wire_codec: str | None = None            # None | "int8"
    codec_block: int = 512                   # absmax block (elems per scale)

    @property
    def n_buckets(self) -> int:
        return self.bucket_plan.n_buckets

    @property
    def n_channels(self) -> int:
        return len(self.channels)

    @property
    def total_elems(self) -> int:
        return self.bucket_plan.total_elems

    @property
    def world(self) -> int:
        w = 1
        for p in self.axis_sizes:
            w *= p
        return w

    def bucket_channel(self, bucket: int) -> int:
        for a in self.channels:
            if bucket in a.buckets:
                return a.channel
        raise KeyError(bucket)

    @property
    def channel_imbalance(self) -> float:
        """max/mean channel load (1.0 = perfectly striped)."""
        loads = [a.elems for a in self.channels]
        mean = sum(loads) / max(len(loads), 1)
        return max(loads) / mean if mean else 1.0

    def predicted_collective_bytes(self) -> dict[str, float]:
        """The dict ``GradientReducer.predicted_collective_bytes`` returned,
        plus the channel-level breakdown."""
        used = self.bucket_plan.used_elems
        out = {
            "bytes_per_device": self.bytes_per_device,
            "grad_bytes": used * 4.0,
            "wire_bytes_per_elem": self.wire_bytes_per_elem,
            "n_channels": float(self.n_channels),
            "channel_imbalance": self.channel_imbalance,
            "messages_per_device": self.messages_per_device,
        }
        if self.arena_layout is not None:
            out.update({
                "arena_bytes_per_device": self.arena_bytes_per_device,
                "arena_messages_per_device": self.arena_messages_per_device,
                "arena_pages": float(self.arena_layout.n_pages),
                "arena_total_bytes": float(self.arena_layout.total_bytes),
                "arena_padding_fraction":
                    self.arena_layout.padding_fraction,
            })
        return out

    def predicted_collective_seconds(self, model: LatencyModel = LatencyModel()
                                     ) -> float:
        """α·messages + bytes/bw for one reduction of this plan."""
        return model.collective_seconds(self.messages_per_device,
                                        self.bytes_per_device)

    def codec_tradeoff(self, model: LatencyModel = LatencyModel(),
                       hbm_bandwidth: float = HBM_BANDWIDTH) -> dict:
        """Price the quantized wire end-to-end: fp32 vs int8+scales.

        Compression is not free — the fused pack+quantize and dequant
        kernels stream the payload through HBM, so the honest comparison is

            t_fp32  = α·msgs + bytes_fp32 / bw_link
            t_codec = α·msgs + bytes_codec / bw_link + hbm_bytes / bw_hbm

        with the same message count on both sides (the codec shrinks hop
        *payloads*, not hop counts).  Kernel HBM traffic per reduction,
        per payload element of ``w = 1 + 4/block`` wire bytes: encode
        reads the fp32 gradient and the error-feedback accumulator,
        writes the accumulator and the wire form (``4+4+4+w``); decode
        reads the wire form and writes fp32 (``w+4``).

        Computed for this plan's codec, or as a what-if at ``codec_block``
        when ``wire_codec`` is ``None`` (``applied`` says which).  Arena
        plans price the arena wire bytes (page padding included).
        """
        nbytes = (self.arena_bytes_per_device if self.arena_layout is not None
                  else self.bytes_per_device)
        msgs = (self.arena_messages_per_device if self.arena_layout is not None
                else self.messages_per_device)
        wpe_q = 1.0 + 4.0 / self.codec_block
        if self.wire_codec is not None:
            codec_bytes = nbytes
            fp32_bytes = nbytes * 4.0 / self.wire_bytes_per_elem
        else:
            fp32_bytes = nbytes * 4.0 / self.wire_bytes_per_elem
            codec_bytes = fp32_bytes * wpe_q / 4.0
        elems = self.total_elems
        kernel_bytes = elems * ((4.0 + 4.0 + 4.0 + wpe_q) + (wpe_q + 4.0))
        kernel_s = kernel_bytes / hbm_bandwidth
        t_fp32 = model.collective_seconds(msgs, fp32_bytes)
        t_codec = model.collective_seconds(msgs, codec_bytes) + kernel_s
        return {
            "applied": self.wire_codec is not None,
            "codec": self.wire_codec or "int8",
            "codec_block": self.codec_block,
            "wire_bytes_fp32": fp32_bytes,
            "wire_bytes_codec": codec_bytes,
            "compression_ratio": fp32_bytes / codec_bytes if codec_bytes
            else 0.0,
            "kernel_hbm_bytes": kernel_bytes,
            "t_kernel_s": kernel_s,
            "t_fp32_s": t_fp32,
            "t_codec_s": t_codec,
            "speedup": t_fp32 / t_codec if t_codec else 0.0,
        }

    def describe(self) -> dict:
        """JSON-friendly summary for the dry-run report."""
        out = {
            "transport": self.transport,
            "axes": list(self.axes),
            "axis_sizes": list(self.axis_sizes),
            "world": self.world,
            "n_buckets": self.n_buckets,
            "total_elems": self.total_elems,
            "padding_waste": self.bucket_plan.padding_waste,
            "channels": [{"channel": a.channel, "buckets": list(a.buckets),
                          "elems": a.elems} for a in self.channels],
            **self.predicted_collective_bytes(),
        }
        if self.arena_layout is not None:
            out["arena"] = self.arena_layout.describe()
        if self.wire_codec is not None:
            out["wire_codec"] = self.wire_codec
            out["codec_block"] = self.codec_block
            out["codec"] = self.codec_tradeoff()
        return out


@dataclass(frozen=True)
class HaloChannel:
    """Units carried by one halo rail, with their payload *bytes* (unlike
    :class:`ChannelAssignment`, whose loads are element counts)."""

    channel: int
    units: tuple[int, ...]     # indices into the unit list, ascending
    bytes: int


@dataclass(frozen=True)
class HaloPlan:
    """The halo-exchange analogue of :class:`CommPlan`: bytes per direction
    × channel for one Cartesian exchange, plus the predicted wire bytes.

    ``units`` are the individual ``ppermute`` payloads (one per direction,
    times the chunk split under the ``chunked`` schedule), labelled
    ``"<axis><dir>[#chunk]"``; ``unit_bytes[i]`` is unit ``i``'s payload
    size.  Each unit crosses the wire exactly once (a ``collective-permute``
    is one hop), so ``bytes_per_device`` is simply the payload total — the
    dry-run's stencil suite checks this against the bytes parsed from the
    lowered HLO.  Self-neighbour exchanges (mesh axis of size 1) still lower
    to a ``collective-permute`` and are therefore counted.
    """

    schedule: str
    axes: tuple[str, ...]          # mesh axis per exchanged direction spec
    axis_sizes: tuple[int, ...]
    local_shape: tuple[int, ...]
    halos: tuple[int, ...]         # face width per spec
    unit_keys: tuple[str, ...]
    unit_bytes: tuple[int, ...]
    channels: tuple[HaloChannel, ...]
    overlap_fraction: float

    @property
    def n_units(self) -> int:
        return len(self.unit_bytes)

    @property
    def bytes_per_device(self) -> float:
        """Predicted wire bytes per device per exchange (one hop per unit)."""
        return float(sum(self.unit_bytes))

    @property
    def messages_per_device(self) -> float:
        """α-term message count: each unit is exactly one ``ppermute``
        payload, i.e. one discrete send per device per exchange."""
        return float(self.n_units)

    def predicted_collective_seconds(self, model: LatencyModel = LatencyModel()
                                     ) -> float:
        """α·messages + bytes/bw for one halo exchange of this plan."""
        return model.collective_seconds(self.messages_per_device,
                                        self.bytes_per_device)

    @property
    def channel_imbalance(self) -> float:
        """max/mean channel load (1.0 = perfectly striped)."""
        loads = [a.bytes for a in self.channels]
        mean = sum(loads) / max(len(loads), 1)
        return max(loads) / mean if mean else 1.0

    def describe(self) -> dict:
        """JSON-friendly summary for the dry-run report."""
        return {
            "schedule": self.schedule,
            "axes": list(self.axes),
            "axis_sizes": list(self.axis_sizes),
            "local_shape": list(self.local_shape),
            "halos": list(self.halos),
            "n_units": self.n_units,
            "units": [{"key": k, "bytes": b}
                      for k, b in zip(self.unit_keys, self.unit_bytes)],
            "channels": [{"channel": a.channel, "units": list(a.units),
                          "bytes": a.bytes} for a in self.channels],
            "bytes_per_device": self.bytes_per_device,
            "messages_per_device": self.messages_per_device,
            "channel_imbalance": self.channel_imbalance,
            "overlap_fraction": self.overlap_fraction,
        }


@dataclass(frozen=True)
class A2APlan:
    """The all-to-all analogue of :class:`CommPlan`: predicted wire cost of
    one expert-parallel dispatch + combine round-trip of a local capacity
    buffer of ``elems_per_device`` elements.

    ``units`` are per-rail all-to-all payloads — ``dispatch#c`` /
    ``combine#c`` per channel rail — and ``unit_bytes[i]`` is the *wire*
    bytes that rail puts in flight per exchange (already scaled by the
    transport: ``(R-1)/R`` of the payload for ring/native all-to-all,
    ``2(R-1)×`` for the honest replicated-psum fallback).  The dry-run's
    moe suite checks ``bytes_per_device`` against the bytes parsed from
    lowered HLO.
    """

    transport: str
    axis: str
    axis_size: int
    elems_per_device: int          # local capacity-buffer elements, one phase
    itemsize: int
    unit_keys: tuple[str, ...]     # "dispatch#c" / "combine#c"
    unit_bytes: tuple[int, ...]
    messages_per_unit: float       # hops per rail exchange (R-1 or 2(R-1))
    channels: tuple[HaloChannel, ...]
    overlap_fraction: float

    @property
    def n_units(self) -> int:
        return len(self.unit_bytes)

    @property
    def bytes_per_device(self) -> float:
        """Predicted wire bytes per device per dispatch+combine round-trip."""
        return float(sum(self.unit_bytes))

    @property
    def messages_per_device(self) -> float:
        """α-term sends per device: hop count per rail, summed over units."""
        return self.messages_per_unit * self.n_units

    @property
    def dispatch_bytes_per_device(self) -> float:
        """Wire bytes of the dispatch half alone (the A/B headline number)."""
        return float(sum(b for k, b in zip(self.unit_keys, self.unit_bytes)
                         if k.startswith("dispatch")))

    def predicted_collective_seconds(self, model: LatencyModel = LatencyModel()
                                     ) -> float:
        """α·messages + bytes/bw for one dispatch+combine round-trip."""
        return model.collective_seconds(self.messages_per_device,
                                        self.bytes_per_device)

    @property
    def channel_imbalance(self) -> float:
        """max/mean channel load (1.0 = perfectly striped)."""
        loads = [a.bytes for a in self.channels]
        mean = sum(loads) / max(len(loads), 1)
        return max(loads) / mean if mean else 1.0

    def describe(self) -> dict:
        """JSON-friendly summary for the dry-run report."""
        return {
            "transport": self.transport,
            "axis": self.axis,
            "axis_size": self.axis_size,
            "elems_per_device": self.elems_per_device,
            "itemsize": self.itemsize,
            "n_units": self.n_units,
            "units": [{"key": k, "bytes": b}
                      for k, b in zip(self.unit_keys, self.unit_bytes)],
            "channels": [{"channel": a.channel, "units": list(a.units),
                          "bytes": a.bytes} for a in self.channels],
            "bytes_per_device": self.bytes_per_device,
            "dispatch_bytes_per_device": self.dispatch_bytes_per_device,
            "messages_per_device": self.messages_per_device,
            "channel_imbalance": self.channel_imbalance,
            "overlap_fraction": self.overlap_fraction,
        }

"""Roofline-term extraction from compiled AOT artifacts.

Three terms per (arch x shape x mesh) cell, v5e constants:

    T_compute    = HLO_FLOPs_per_device  / 197e12      (bf16 MXU peak)
    T_memory     = HLO_bytes_per_device  / 819e9       (HBM bandwidth)
    T_collective = ALPHA_S * messages_per_device
                 + wire_bytes_per_device / 50e9        (per-link ICI)

``cost_analysis`` supplies FLOPs/bytes; collective wire bytes are parsed
from the optimized HLO text: every collective op's result shape is
converted to per-device bytes-on-the-wire with the standard ring formulas
(p from its replica-group size).  Models are fully unrolled, so no
while-loop trip-count scaling is needed — the parser asserts that.

The α term (``repro.comm.plan.LatencyModel``) prices per-message launch
latency: it is what separates two tiny all-reduces per CG iteration from
one fused one, which bandwidth-only accounting cannot see.  Cells that do
not supply a message count keep the pure-bandwidth behaviour
(``messages_per_device`` defaults to 0).
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

from repro.comm.plan import ALPHA_S, HBM_BANDWIDTH, LINK_BANDWIDTH, V5E

PEAK_FLOPS = V5E.bf16_flops  # bf16 per chip
HBM_BW = HBM_BANDWIDTH       # bytes/s per chip
ICI_BW = LINK_BANDWIDTH      # bytes/s per link (one direction); single
                             # source in repro.comm.plan so the roofline and
                             # LatencyModel β terms can never desync (and
                             # CommPlan.codec_tradeoff prices pack+quantize
                             # kernel time against the same HBM number)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "token": 0,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"=\s*((?:\([^)]*\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?))\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_PAIRS_RE = re.compile(r"source_target_pairs=\{")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        # iota replica groups: [num_groups, group_size]
        return int(m.group(2))
    return 2  # conservative default


@dataclass
class CollectiveStats:
    wire_bytes: float = 0.0
    op_bytes: dict = field(default_factory=dict)
    op_counts: dict = field(default_factory=dict)
    messages: float = 0.0        # per-device sends (ring hops / ppermutes) —
                                 # same unit as Transport
                                 # .predicted_messages_per_device, so the
                                 # roofline α term prices HLO-parsed and
                                 # plan-predicted traffic identically
    while_loops: int = 0

    def add(self, kind: str, b: float, hops: float = 1.0):
        self.wire_bytes += b
        self.op_bytes[kind] = self.op_bytes.get(kind, 0.0) + b
        self.op_counts[kind] = self.op_counts.get(kind, 0) + 1
        self.messages += hops


def collective_wire_bytes(hlo_text: str) -> CollectiveStats:
    """Per-device bytes placed on ICI links, summed over collective ops.

    Formulas (result-shape based, ring algorithms):
      collective-permute : result            (one hop)
      all-gather         : result * (p-1)/p
      all-reduce         : result * 2(p-1)/p
      reduce-scatter     : result * (p-1)
      all-to-all         : result * (p-1)/p
    ``-start``/``-done`` async pairs are counted once (on the start op).
    ``messages`` accumulates the matching ring hop counts (1 per permute,
    ``2(p−1)`` per all-reduce, ``p−1`` otherwise) for the α latency term.
    """
    stats = CollectiveStats()
    seen_done = 0
    for line in hlo_text.splitlines():
        if "-done(" in line and any(c in line for c in _COLLECTIVES):
            seen_done += 1
            continue  # counted at -start
        m = _OP_RE.search(line)
        if not m:
            if re.search(r"=\s*while\(", line) or " while(" in line:
                stats.while_loops += 1
            continue
        type_str, kind = m.group(1), m.group(2)
        nbytes = _shape_bytes(type_str)
        if kind == "collective-permute":
            stats.add(kind, nbytes)
            continue
        p = _group_size(line)
        if p <= 1:
            continue
        if kind == "all-gather":
            stats.add(kind, nbytes * (p - 1) / p, hops=p - 1)
        elif kind == "all-reduce":
            stats.add(kind, nbytes * 2 * (p - 1) / p, hops=2 * (p - 1))
        elif kind == "reduce-scatter":
            stats.add(kind, nbytes * (p - 1), hops=p - 1)
        elif kind == "all-to-all":
            stats.add(kind, nbytes * (p - 1) / p, hops=p - 1)
    return stats


@dataclass
class Roofline:
    flops_per_device: float
    hbm_bytes_per_device: float
    wire_bytes_per_device: float
    model_flops: float = 0.0
    overlap_fraction: float = 0.0   # CommSchedule.overlap_fraction: share of
                                    # collective traffic issued while compute
                                    # remains (0 = serialised after compute)
    messages_per_device: float = 0.0  # collective launches (α latency term)
    padding_wire_bytes_per_device: float = 0.0  # arena page padding that
                                    # rides the fused collectives: wasted
                                    # but *real* wire bytes (repro.mem)
    alpha_s: float = ALPHA_S
    link_bandwidth: float = ICI_BW  # β term; a tuning-DB record replaces
                                    # both constants with *measured* ones
                                    # (see Roofline.from_latency)
    peak_flops: float = PEAK_FLOPS  # chip the program is priced for
    hbm_bandwidth: float = HBM_BW   # (repro.comm.plan.DEVICE_PEAKS)

    @classmethod
    def from_latency(cls, model, **kw) -> "Roofline":
        """Roofline whose α/β constants come from a
        :class:`~repro.comm.plan.LatencyModel` — typically one rebuilt
        from a tuning-DB record (``LatencyModel.from_record``) so the cell
        is priced with measured rather than guessed constants."""
        return cls(alpha_s=model.alpha_s, link_bandwidth=model.bandwidth,
                   **kw)

    @property
    def t_compute(self) -> float:
        return self.flops_per_device / self.peak_flops

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes_per_device / self.hbm_bandwidth

    @property
    def t_collective(self) -> float:
        """α·messages + bytes/bw (pure bandwidth when no count supplied).
        Arena page padding is folded into the β term: fused spans carry it
        across the wire, so the prediction charges for it."""
        return (self.alpha_s * self.messages_per_device
                + (self.wire_bytes_per_device
                   + self.padding_wire_bytes_per_device)
                / self.link_bandwidth)

    @property
    def t_exposed_collective(self) -> float:
        """Collective time left *exposed* after hiding under the compute the
        schedule makes overlappable: ``max(0, t_collective −
        overlap_fraction · t_compute)``.  Equals ``t_collective`` for an
        ``accumulate_then_reduce`` schedule (overlap 0); never exceeds it."""
        hidden = min(1.0, max(0.0, self.overlap_fraction)) * self.t_compute
        return max(0.0, self.t_collective - hidden)

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def bound_time(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def bound_time_overlapped(self) -> float:
        """Step-time bound when the schedule's overlap is realised: only the
        exposed collective time serialises with compute."""
        return max(self.t_compute, self.t_memory, self.t_exposed_collective)

    @property
    def compute_fraction(self) -> float:
        """How close the cell is to the compute roofline (1.0 = perfectly
        compute-bound; the §Perf score)."""
        t = self.bound_time
        return self.t_compute / t if t > 0 else 0.0

    def useful_flops_ratio(self, n_devices: int) -> float:
        if self.flops_per_device <= 0:
            return 0.0
        return self.model_flops / (self.flops_per_device * n_devices)

    def as_dict(self, n_devices: int) -> dict:
        return {
            "flops_per_device": self.flops_per_device,
            "hbm_bytes_per_device": self.hbm_bytes_per_device,
            "wire_bytes_per_device": self.wire_bytes_per_device,
            "messages_per_device": self.messages_per_device,
            "padding_wire_bytes_per_device":
                self.padding_wire_bytes_per_device,
            "alpha_s": self.alpha_s,
            "link_bandwidth": self.link_bandwidth,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "t_exposed_collective_s": self.t_exposed_collective,
            "overlap_fraction": self.overlap_fraction,
            "bottleneck": self.bottleneck,
            "compute_fraction": self.compute_fraction,
            "bound_time_overlapped_s": self.bound_time_overlapped,
            "model_flops": self.model_flops,
            "useful_flops_ratio": self.useful_flops_ratio(n_devices),
        }


def model_flops_estimate(n_params_active: int, tokens: int,
                         kind: str) -> float:
    """6·N·D for training; 2·N·D for inference forward passes."""
    mult = 6.0 if kind == "train" else 2.0
    return mult * n_params_active * tokens

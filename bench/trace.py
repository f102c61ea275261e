"""Reduce a profiler trace to the numbers the per-layer metrics read.

Input: the ``.xplane.pb`` that ``jax.profiler`` writes.  On a TPU each chip
is a plane ``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per
HLO operation run on the device; the host's ``TraceAnnotation`` spans are
on the ``/host:CPU`` plane, on the same clock.  (On the CPU backend the
operations run on host threads named ``tf_XLA...``; the same reduction
reads them there, which is what the recorded test trace holds.)

Definitions:

* window: from the start of the first host span of the cell's step name to
  the end of the last one;
* busy: the union of the operation intervals of a device inside the window
  (averaged over the devices);
* collective time: the operations whose HLO opcode is a collective (the
  CPU backend names a collective-permute ``ppermute``); its
  exposed part: the collective intervals not covered by any other
  operation on the same device;
* idle gaps: the holes in a device's busy union inside the window, each
  named after the innermost host span that covers its middle.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "collective-broadcast",
               "send", "recv", "ppermute")
SPAN_PREFIX = "bench."
_CPU_OP = re.compile(r"^[\w.\-]+$")   # an HLO instruction name
_CPU_NOISE = ("Rendezvous", "InvokeRendezvous")
_SUFFIX = re.compile(r"(\.\d+)+$")


_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")


def instruction(op: str) -> str:
    """A TPU trace names an op by its HLO text (``fn.9 = (...)
    custom-call(...)``); the instruction is what stands before `` = ``."""
    return op.split(" = ", 1)[0].strip().lstrip("%")


def base_name(op: str) -> str:
    """The instruction's name without its number: ``fusion.12`` ->
    ``fusion``."""
    return _SUFFIX.sub("", instruction(op))


def opcode(op: str) -> str:
    """The HLO opcode of an op (its name where the trace gives no text),
    with a custom call's target: ``custom-call:tpu_custom_call``."""
    if " = " not in op:
        return base_name(op)
    m = _OPCODE.search(op.split(" = ", 1)[1])
    code = m.group(1) if m else base_name(op)
    t = re.search(r'custom_call_target="([^"]+)"', op)
    return f"{code}:{t.group(1)}" if code == "custom-call" and t else code


def is_collective(op: str) -> bool:
    return opcode(op).startswith(COLLECTIVES)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def extract(path: str) -> tuple[dict, list]:
    """``(ops, spans)``: ``ops[device] = [(name, start_ns, end_ns)]`` and
    ``spans = [(name, start_ns, end_ns)]`` of the host's ``bench.`` spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: dict = defaultdict(list)
    spans: list = []
    tpu = re.compile(r"^/device:TPU:(\d+)$")
    for plane in pd.planes:
        m = tpu.match(plane.name)
        for line in plane.lines:
            if m and line.name == "XLA Ops":
                ops[int(m.group(1))] += [(e.name, e.start_ns, e.end_ns)
                                         for e in line.events]
            elif plane.name == "/host:CPU":
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns, e.end_ns))
                    elif (line.name.startswith("tf_XLA") and e.duration_ns > 0
                          and _CPU_OP.match(e.name)
                          and e.name not in _CPU_NOISE):
                        ops["cpu"].append((e.name, e.start_ns, e.end_ns))
    if any(k != "cpu" for k in ops):
        ops.pop("cpu", None)
    return dict(ops), spans


def union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _length(ivs) -> float:
    return sum(e - s for s, e in ivs)


def _clip(ivs, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in ivs if e > lo and s < hi]


def _subtract(a, b):
    """``a`` minus ``b``; both unions (sorted, disjoint)."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _span_at(spans, t):
    inner = None
    for name, s, e in spans:
        if s <= t <= e and (inner is None or e - s < inner[2] - inner[1]):
            inner = (name, s, e)
    return inner[0][len(SPAN_PREFIX):] if inner else "outside_spans"


def reduce(ops: dict, spans: list, step_span: str, top: int = 10) -> dict:
    """The window's numbers (seconds), averaged over the devices."""
    steps = [(s, e) for n, s, e in spans if n == SPAN_PREFIX + step_span]
    if not steps or not ops:
        return {"steps": 0}
    lo, hi = min(s for s, _ in steps), max(e for _, e in steps)
    n_dev = len(ops)
    busy = coll = exposed = 0.0
    by_name: dict = defaultdict(float)
    by_code: dict = defaultdict(float)
    gaps: list = []
    for evs in ops.values():
        evs = [(n, s, e) for n, s, e in evs if e > lo and s < hi]
        all_u = union(_clip([(s, e) for _, s, e in evs], lo, hi))
        busy += _length(all_u)
        c_u = union(_clip([(s, e) for n, s, e in evs if is_collective(n)],
                          lo, hi))
        other = union(_clip([(s, e) for n, s, e in evs
                             if not is_collective(n)], lo, hi))
        coll += _length(c_u)
        exposed += _length(_subtract(c_u, other))
        for n, s, e in evs:
            label = f"{instruction(n)} {opcode(n)}"
            by_name[label] += min(e, hi) - max(s, lo)
            by_code[opcode(n)] += min(e, hi) - max(s, lo)
        edges = [lo] + [x for iv in all_u for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((_span_at(spans, (a + b) / 2), b - a))
    ns = 1e-9
    named_gaps: dict = defaultdict(float)
    for name, g in gaps:
        named_gaps[name] += g
    return {
        "steps": len(steps),
        "window_s": (hi - lo) * ns,
        "busy_s": busy / n_dev * ns,
        "collective_s": coll / n_dev * ns,
        "collective_exposed_s": exposed / n_dev * ns,
        "op_s": {k: v / n_dev * ns for k, v in by_name.items()},
        "opcode_s": {k: v / n_dev * ns for k, v in by_code.items()},
        "device_ops": [[k, v / n_dev * ns] for k, v in
                       sorted(by_name.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v / n_dev * ns] for k, v in
                      sorted(named_gaps.items(), key=lambda kv: -kv[1])[:top]],
    }


def op_seconds(red: dict, pattern: str, key: str = "op_s") -> float | None:
    """Device seconds of the operations whose name (``key="opcode_s"``:
    opcode) contains ``pattern`` (None when the trace has none)."""
    hits = [v for k, v in red.get(key, {}).items() if pattern in k]
    return sum(hits) if hits else None

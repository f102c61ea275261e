"""Phase-span tracing: host spans on the profiler's clock, also kept as
wall-clock durations exportable as Chrome trace JSON.

A :class:`Span` brackets one phase of a step (data, dispatch, collective
wait, checkpoint, decode...).  It opens a ``jax.profiler.TraceAnnotation``
named ``repro.<name>`` with its labels as arguments, so under
``jax.profiler.start_trace`` the span lands on the ``/host:CPU`` plane of
the trace, on the clock of the device's ``XLA Ops`` lines, nested in any
enclosing annotation; it also takes ``time.perf_counter`` stamps.  Because
jax dispatch is asynchronous, a span that should account for *device* work
must fence: ``sp.fence(tree)`` registers a pytree that the span
``jax.block_until_ready``-s on exit, so the recorded duration covers the
device execution the phase launched, not just the Python that enqueued it.

Besides the profiler trace, spans export two ways:

* mirrored onto the :class:`~repro.obs.bus.MetricsBus` as ``span`` JSONL
  records (what the report CLI aggregates), and
* as Chrome ``trace_event`` complete events (``"ph": "X"``, microsecond
  timestamps) via :meth:`Tracer.export_chrome` — the resulting
  ``trace.json`` loads directly in Perfetto / ``chrome://tracing``.

The disabled tracer hands out a shared no-op span: no annotation, no clock
reads, no allocation, no fencing — the opt-out leaves the step loop
untouched.
"""

from __future__ import annotations

import json
import os
import time

from repro.obs.bus import NULL_BUS, _jsonable

PROFILER_PREFIX = "repro."   # a span's name in the profiler trace


class Span:
    """One phase; use as a context manager (see :meth:`Tracer.span`)."""

    __slots__ = ("_tracer", "name", "labels", "_fence", "_note", "t0",
                 "dur_s")

    def __init__(self, tracer: "Tracer", name: str, labels: dict):
        self._tracer = tracer
        self.name = name
        self.labels = labels
        self._fence = None
        self._note = None
        self.t0 = None
        self.dur_s = None

    def fence(self, tree):
        """Register a pytree to ``jax.block_until_ready`` before the span
        closes (device work launched in the span lands in its duration).
        Returns ``tree`` so call sites can fence inline."""
        self._fence = tree
        return tree

    def __enter__(self) -> "Span":
        from jax.profiler import TraceAnnotation  # lazy, as in the fence

        self._note = TraceAnnotation(PROFILER_PREFIX + self.name,
                                     **self.labels)
        self._note.__enter__()
        self.t0 = self._tracer._clock()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._fence is not None:
            import jax  # lazy: the tracer itself stays jax-free

            jax.block_until_ready(self._fence)
            self._fence = None
        self.dur_s = self._tracer._clock() - self.t0
        self._note.__exit__(exc_type, exc, tb)
        self._note = None
        self._tracer._record(self.name, self.t0, self.dur_s, self.labels)
        return False


class _NullSpan:
    """Shared no-op span: enter/exit/fence do nothing."""

    __slots__ = ()
    name = None
    dur_s = None

    def fence(self, tree):
        return tree

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


NULL_SPAN = _NullSpan()


class Tracer:
    """Span factory + Chrome ``trace_event`` exporter."""

    def __init__(self, bus=NULL_BUS, *, enabled: bool = True,
                 clock=time.perf_counter, pid: int | None = None,
                 tid: int = 0):
        self.enabled = enabled
        self.bus = bus
        self._clock = clock
        self.pid = os.getpid() if pid is None else pid
        self.tid = tid
        self.epoch = clock() if enabled else 0.0
        # (name, t0, dur_s, labels) tuples; t0 on the clock's timeline
        self.events: list[tuple] = []

    def span(self, name: str, **labels):
        if not self.enabled:
            return NULL_SPAN
        return Span(self, name, labels)

    def _record(self, name: str, t0: float, dur_s: float,
                labels: dict) -> None:
        self.events.append((name, t0, dur_s, labels))
        self.bus.span(name, dur_s, **labels)

    def export_chrome(self, path: str) -> str:
        """Write the spans as a Perfetto-loadable Chrome trace and return
        the path.  Complete (``"ph": "X"``) events, µs since the tracer's
        epoch, labels carried in ``args``."""
        trace_events = [
            {"name": name, "ph": "X", "cat": "obs",
             "ts": (t0 - self.epoch) * 1e6, "dur": dur_s * 1e6,
             "pid": self.pid, "tid": self.tid, "args": labels or {}}
            for name, t0, dur_s, labels in self.events
        ]
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump({"traceEvents": trace_events,
                       "displayTimeUnit": "ms"}, f, default=_jsonable)
        return path


class _NullTracer:
    enabled = False
    events: tuple = ()
    bus = NULL_BUS

    def span(self, name, **labels):
        return NULL_SPAN

    def export_chrome(self, path):
        return None


NULL_TRACER = _NullTracer()

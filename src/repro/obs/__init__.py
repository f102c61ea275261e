"""repro.obs — runtime telemetry bus, phase-span tracing, drift detection.

The measurement half of the repo's predict-everything architecture: the
dry-run/roofline/TuningDB layers *predict* bytes, messages and seconds;
this package *measures* live runs through one spine —

* :class:`~repro.obs.bus.MetricsBus` — counters/gauges/histograms with
  labels, JSONL sink (``events.jsonl``);
* :class:`~repro.obs.trace.Tracer` — host phase spans with optional
  ``block_until_ready`` fencing; each also enters the ``jax.profiler``
  trace as ``repro.<name>`` (on the device's clock) and is exported as
  Chrome ``trace_event`` JSON (Perfetto-loadable ``trace.json``);
* the ``compiles`` counter — every executable the process obtains while
  an :class:`Obs` is live (a backend compile or a persistent-cache load);
* :class:`~repro.obs.drift.DriftDetector` — per-step measured-vs-predicted
  comparison emitting ``model_error`` gauges and ``drift_alarm`` events;
* :mod:`repro.obs.schema` — the shared ``BENCH_<name>.json`` row schema;
* ``python -m repro.obs.report <run_dir>`` — the offline summarizer.

Everything importable here is stdlib-only (jax is touched lazily: in
spans, in the compile listener an :class:`Obs` registers, and in the
``repro.obs.predict`` bridge), so the report CLI stays light.
``ObsConfig(enabled=False)`` — or simply a ``None`` config — resolves to
:data:`NULL_OBS`, whose every operation is a no-op: an uninstrumented step
and an obs-disabled step lower to the identical HLO (pinned in
``tests/test_obs.py``).
"""

from __future__ import annotations

import os
import weakref
from dataclasses import dataclass

from repro.obs.bus import MetricsBus, NULL_BUS
from repro.obs.drift import DriftDetector, DriftSample
from repro.obs.schema import (bench_record, load_bench_record, rows_from_csv,
                              write_bench_record)
from repro.obs.trace import NULL_SPAN, NULL_TRACER, Span, Tracer

__all__ = [
    "ObsConfig", "Obs", "make_obs", "NULL_OBS",
    "MetricsBus", "NULL_BUS", "Tracer", "Span", "NULL_TRACER", "NULL_SPAN",
    "DriftDetector", "DriftSample",
    "bench_record", "write_bench_record", "load_bench_record",
    "rows_from_csv",
]


@dataclass(frozen=True)
class ObsConfig:
    """Everything the runtime needs to instrument (or not instrument) a run.

    ``enabled=False`` is the hard opt-out: :func:`make_obs` returns
    :data:`NULL_OBS` and no clock, file or dict is ever touched.  With
    ``run_dir=None`` the bus aggregates in memory only (no JSONL sink, no
    trace file) — useful for tests and embedded use."""

    enabled: bool = True
    run_dir: str | None = None
    trace: bool = True                 # collect spans + export trace.json
    flush_every: int = 64              # JSONL buffer flush cadence
    # drift detection (active only when a prediction is available)
    drift_threshold: float = 0.5       # |rolling median rel err| alarm bar
    drift_window: int = 8
    drift_warmup: int = 1              # leading samples excluded (compile)
    drift_min_samples: int = 3
    predicted_step_s: float | None = None  # explicit prediction (wins)
    predict: bool = False              # AOT-lower + roofline at init
    tuned_db: str | None = None        # price with measured α/β from this DB

    @classmethod
    def off(cls) -> "ObsConfig":
        return cls(enabled=False)


class Obs:
    """The bundle a run holds: one bus + one tracer + config, with the
    convenience delegates hot loops call."""

    enabled = True

    def __init__(self, cfg: ObsConfig):
        self.cfg = cfg
        self.bus = MetricsBus(cfg.run_dir, flush_every=cfg.flush_every)
        self.tracer = Tracer(self.bus, enabled=cfg.trace)
        _count_compiles(self)

    # -- delegates -----------------------------------------------------------

    def span(self, name: str, **labels):
        return self.tracer.span(name, **labels)

    def counter(self, name: str, value: float = 1.0, **labels):
        return self.bus.counter(name, value, **labels)

    def gauge(self, name: str, value: float, **labels) -> None:
        self.bus.gauge(name, value, **labels)

    def observe(self, name: str, value: float, **labels) -> None:
        self.bus.observe(name, value, **labels)

    def event(self, name: str, **fields) -> None:
        self.bus.event(name, **fields)

    # -- drift ---------------------------------------------------------------

    def drift_detector(self, predicted_s: float,
                       metric: str = "step_time_s",
                       source: str = "roofline") -> DriftDetector:
        """A detector wired to this bus with the config's thresholds."""
        return DriftDetector(predicted_s, metric=metric, bus=self.bus,
                             threshold=self.cfg.drift_threshold,
                             window=self.cfg.drift_window,
                             warmup=self.cfg.drift_warmup,
                             min_samples=self.cfg.drift_min_samples,
                             source=source)

    # -- lifecycle -----------------------------------------------------------

    def finish(self) -> dict:
        """Flush the sink and (when a run_dir is bound) export the Chrome
        trace; returns the artifact paths."""
        _COUNTING.discard(self)
        trace_path = None
        if (self.cfg.run_dir is not None and self.tracer.enabled
                and self.tracer.events):
            trace_path = self.tracer.export_chrome(
                os.path.join(self.cfg.run_dir, "trace.json"))
        self.bus.close()
        return {"events": self.bus.path, "trace": trace_path}


# Every live Obs counts the executables the process obtains.  JAX times
# ``compile_or_get_cached`` under this event, so it fires once per backend
# compile and once per persistent-cache load, never on an in-memory hit.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_COUNTING: "weakref.WeakSet[Obs]" = weakref.WeakSet()
_listening = False


def _on_duration(event: str, duration: float, **kw) -> None:
    if event == COMPILE_EVENT:
        for obs in list(_COUNTING):
            obs.bus.counter("compiles", fun=kw.get("fun_name", ""))


def _count_compiles(obs: "Obs") -> None:
    global _listening
    _COUNTING.add(obs)
    if not _listening:
        import jax.monitoring  # lazy: the package stays jax-free

        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True


class _NullObs:
    """`Obs` with every operation a no-op (the ``enabled=False`` lowering)."""

    enabled = False
    cfg = ObsConfig(enabled=False)
    bus = NULL_BUS
    tracer = NULL_TRACER

    def span(self, name, **labels):
        return NULL_SPAN

    def counter(self, name, value=1.0, **labels):
        return 0.0

    def gauge(self, name, value, **labels):
        pass

    def observe(self, name, value, **labels):
        pass

    def event(self, name, **fields):
        pass

    def drift_detector(self, predicted_s, metric="step_time_s",
                       source="roofline"):
        return None

    def finish(self):
        return {"events": None, "trace": None}


NULL_OBS = _NullObs()


def make_obs(cfg: ObsConfig | None) -> Obs | _NullObs:
    """The single constructor every subsystem funnels through: a real
    :class:`Obs` when ``cfg.enabled``, else the shared :data:`NULL_OBS`."""
    if cfg is None or not cfg.enabled:
        return NULL_OBS
    return Obs(cfg)

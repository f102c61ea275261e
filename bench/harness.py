"""The harness: find a cell's pieces by name, run set-up, the window, the
traced slice and the check, and print the result line.

Everything a cell needs is found by the names in ``BENCHMARK.json``:

* ``bench/configs/<config>.json``: the configuration (and, under
  ``reference``, the module of its plain reference);
* ``bench/traffic/<traffic>.json``: the traffic mix (and, under
  ``driver``, the module under ``bench/drivers`` that drives the program);
* ``bench/metrics/<metric>.py`` (dots as underscores): a per-layer reader;
* ``bench/limits/<workload>.json``: the limit of each number the check
  compares.
"""

from __future__ import annotations

import importlib
import json
import math
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
OUT = os.path.join(ROOT, ".bench_out")


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def spec() -> dict:
    return load_json(ROOT, "BENCHMARK.json")


def cell_parts(workload: str):
    """``(cell, cfg, mix, limits, end_to_end, per_layer)`` for a workload."""
    s = spec()
    cells = {w["name"]: w for w in s["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    cfg = load_json(BENCH, "configs", cell["config"] + ".json")
    mix = load_json(BENCH, "traffic", cell["traffic"] + ".json")
    limits = load_json(BENCH, "limits", workload + ".json")

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    e2e = [m for m in s["end_to_end"] if mine(m)]
    per_layer = [m for m in s["per_layer"] if mine(m)]
    return cell, cfg, mix, limits, e2e, per_layer


def driver_class(mix: dict):
    return importlib.import_module(f"bench.drivers.{mix['driver']}").Driver


def reader(metric_name: str):
    mod = metric_name.replace(".", "_").replace("-", "_")
    return importlib.import_module(f"bench.metrics.{mod}").read


def enable_cache() -> str:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def devices_for(chips: int, require_chip: bool):
    """The devices a cell runs on; raises unless JAX finds that many TPU
    chips (``require_chip=False`` is for the benchmark's own CPU tests)."""
    import jax

    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise RuntimeError(f"needs a TPU; JAX found {devs[0].platform!r} "
                           f"devices")
    if len(devs) < chips:
        raise RuntimeError(f"the cell needs {chips} chips; JAX found "
                           f"{len(devs)}")
    return devs


def peak_bytes(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def traced_readings(drv, trace_dir: str) -> dict:
    """Profile the driver's traced steps, reduce the trace, and return the
    reduction and the driver's inputs for the metric readers."""
    import jax

    from bench import trace

    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    try:
        drv.traced_steps()
    finally:
        jax.profiler.stop_trace()
    ops, spans = trace.extract(trace.find_xplane(trace_dir))
    red = trace.reduce(ops, spans, drv.step_span)
    shutil.rmtree(trace_dir, ignore_errors=True)
    with open(trace_dir.rstrip("/") + ".ops.json", "w") as f:
        json.dump(red, f)
    return {"trace": red, "inputs": drv.layer_inputs(red)}


def run_cell(workload: str, seed: int, seconds: float, traced: bool, *,
             t_start: float, require_chip: bool = True, log=None,
             parts: tuple | None = None, fault: str | None = None) -> dict:
    """One run of a cell; returns the result line as a dict.  ``parts``
    (tests only) stands in for ``cell_parts(workload)``."""
    from bench.peaks import peaks_for

    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    cell, cfg, mix, limits, e2e, per_layer = parts or cell_parts(workload)
    devs = devices_for(cell["chips"], require_chip)
    used = devs[:cell["chips"]]
    kind = used[0].device_kind
    peaks = peaks_for(kind) if require_chip else {}
    drv = driver_class(mix)(cell, cfg, mix, used, seed, log=log, fault=fault)
    drv.setup()
    setup_s = time.time() - t_start - drv.excluded_s
    win = drv.window(seconds)
    metrics: dict = {}
    device = {"platform": used[0].platform, "kind": kind, "count": len(devs)}
    breakdown = None
    if traced:
        r = traced_readings(drv, os.path.join(OUT, "trace", workload))
        red = r["trace"]
        r.update(peaks=peaks, chips=cell["chips"])
        for m in per_layer:
            v = reader(m["name"])(r)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=red.get("busy_s", 0.0),
                      window_s=red.get("window_s", 0.0))
        breakdown = {"device_ops": red.get("device_ops", []),
                     "idle_gaps": red.get("idle_gaps", [])}
    else:
        vals = dict(win["metrics"], setup_s=setup_s)
        for m in e2e:
            metrics[m["name"]] = {"value": vals[m["name"]], "unit": m["unit"]}
    device["memory_peak_bytes"] = peak_bytes(used)
    drv.release()
    t0 = time.time()
    got = drv.check()
    checks = {k: {"value": v, "limit": limits[k]} for k, v in got.items()
              if not k.startswith("_")}
    correct = bool(checks) and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())
    log(f"check took {time.time() - t0:.1f} s; details "
        f"{ {k: v for k, v in got.items() if k.startswith('_')} }")
    out = {"correct": correct, "attempted": win["attempted"],
           "failed": win["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out

"""Benchmark harness helpers.

Measured benchmarks run in fresh subprocesses with 8 XLA host devices: the
paper's *algorithmic* effects (per-tensor call overhead, fusion, chunking,
schedule) are real and measurable on shared-memory devices even though the
wire is a memcpy; wire-level effects live in the dry-run roofline instead
(EXPERIMENTS.md explains the split).
"""

from __future__ import annotations

import inspect
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def run_on_devices(script: str, n_devices: int = 8, timeout: int = 1200) -> str:
    env = dict(os.environ)
    # host devices only (CPU rows): on a machine with an accelerator the
    # child must never take it from the parent
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise RuntimeError(f"benchmark subprocess failed:\n{proc.stderr[-4000:]}")
    return proc.stdout


class Timing(float):
    """Median seconds that still *is* a float (every bench call site keeps
    working), carrying the dispersion the tuner's fitter weights by."""

    t_min: float
    t_max: float
    samples: tuple

    def __new__(cls, samples):
        ts = sorted(float(t) for t in samples)
        mid = len(ts) // 2
        # true median: mean of the middle pair for even sample counts
        # (ts[len//2] alone is the *upper* median — biased high).  Parity
        # via & 1, not modulo: this source is embedded verbatim in bench
        # scripts that then go through printf-style substitution, where a
        # bare percent sign is a format character
        med = ts[mid] if len(ts) & 1 else 0.5 * (ts[mid - 1] + ts[mid])
        self = super().__new__(cls, med)
        self.t_min = ts[0]
        self.t_max = ts[-1]
        self.samples = tuple(ts)
        return self

    @property
    def spread(self):
        return self.t_max - self.t_min


def time_call(fn, *args, warmup=1, iters=3):
    """Median wall-seconds of ``iters`` blocked calls, as a :class:`Timing`."""
    import time

    import jax

    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return Timing(ts)


# the same implementation, embedded verbatim in bench subprocess scripts —
# one source of truth for module importers and TIMER_SNIPPET consumers
TIMER_SNIPPET = "\n" + inspect.getsource(Timing) + "\n" + \
    inspect.getsource(time_call) + "\n"


def _obs_schema():
    # the harness may run without PYTHONPATH=src (python benchmarks/run.py)
    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    from repro.obs import schema

    return schema


def bench_rows(stdout: str) -> list[dict]:
    """Parse a bench's CSV stdout into schema row dicts (repro.obs.schema)."""
    return _obs_schema().rows_from_csv(stdout)


def write_bench_json(out_dir: str, name: str, stdout: str,
                     meta: dict | None = None) -> str:
    """Write one ``BENCH_<name>.json`` under ``out_dir`` from a bench's CSV
    stdout, through the shared ``repro.obs.bench/v1`` schema; returns the
    path."""
    schema = _obs_schema()
    return schema.write_bench_record(out_dir, name, bench_rows(stdout),
                                     meta=meta)

"""Shared fixtures.  NOTE: no XLA_FLAGS here — unit/smoke tests must see the
real single CPU device (the 512-device override is exclusively the dry-run's;
distributed tests spawn subprocesses that set their own flag)."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)


@pytest.fixture(scope="session")
def rng():
    import numpy as np

    return np.random.RandomState(0)


def run_distributed(script: str, n_devices: int = 8, timeout: int = 560,
                    extra_flags: str = "") -> str:
    """Run ``script`` in a fresh interpreter with N host devices; returns
    stdout.  Raises on non-zero exit.  ``extra_flags`` appends to XLA_FLAGS
    (e.g. ``--xla_disable_hlo_passes=fusion`` for the bitwise cross-schedule
    stencil tests, which must exclude backend fusion heuristics)."""
    import subprocess

    env = dict(os.environ)
    # host devices only: on a machine with an accelerator the child must
    # never take it from the parent
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={n_devices}"
                        + (f" {extra_flags}" if extra_flags else ""))
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise AssertionError(
            f"distributed script failed (rc={proc.returncode}):\n"
            f"--- stdout ---\n{proc.stdout}\n--- stderr ---\n{proc.stderr[-4000:]}")
    return proc.stdout


def host_trace_events(fn, trace_dir) -> list:
    """Run ``fn()`` under ``jax.profiler`` and return the events of the
    trace's ``/host:CPU`` plane as ``(name, start_ns, end_ns, stats)``."""
    import glob

    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(trace_dir))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)) for e in line.events]
    return out

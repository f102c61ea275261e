"""The four-chip training cell (held back from BENCHMARK.json) at a tiny
size on four CPU host devices (a child process, which sets its own device
count): a sound run is correct, and runs with each fault the cell can have
planted are not (the gradient exchange left out, half of the batch left
out, the state left unchanged)."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SCRIPT = """
import json, sys
sys.path[:0] = [{here!r}]
from tiny_cells import run
out = {{f or "sound": run({w!r}, fault=f) for f in (None, *{faults!r})}}
print(json.dumps({{k: [v["correct"], v["checks"], v["device"]["count"]]
                  for k, v in out.items()}}))
"""


@pytest.mark.parametrize("w, faults", [
    ("minicpm-2b.train.dp4", ("no_exchange", "half_batch", "stale_state"))])
def test_sound_and_faulty_runs(w, faults):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    here = os.path.dirname(os.path.abspath(__file__))
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(here=here, w=w, faults=faults)],
        env=env, capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    ok, checks, count = got["sound"]
    assert ok and count == 4, checks
    for f in faults:
        assert not got[f][0], (f, got[f][1])

"""``chip_smoke.py`` off the chip: its phases at smoke scale on the CPU
(kernels in interpret mode), its depth fit, and its refusal to report a
result without a TPU."""

import os
import shutil
import subprocess
import sys

import pytest

from conftest import REPO, run_distributed

if REPO not in sys.path:
    sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from repro.configs import reduced_config  # noqa: E402

SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _run_script(cwd, script=SCRIPT, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_import_touches_no_devices():
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, sys.argv[1]); import chip_smoke; "
         "from jax._src import xla_bridge as xb; "
         "print(xb.backends_are_initialized())", REPO],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "False"


def test_entry_point_refuses_a_cpu_only_backend():
    out = _run_script(REPO)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "needs a TPU" in out.stderr


def test_entry_point_fails_without_the_repo(tmp_path):
    script = shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    out = _run_script(tmp_path, script=str(script))
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout


@pytest.mark.parametrize("peaks, budget, want", [
    ({n: 10 + 3 * n for n in range(1, 17)}, 40, 10),     # linear
    ({n: 10 + 3 * n for n in range(1, 17)}, 1000, 16),   # full depth
    ({n: 10 + 3 * n + (5 if n > 6 else 0)                # grows faster
      for n in range(1, 17)}, 40, 8),                    # than predicted
    ({n: 30 + 20 * n for n in range(1, 17)}, 60, 1),
])
def test_fit_depth_keeps_the_most_layers_that_fit(peaks, budget, want):
    n, seen = chip_smoke.fit_depth(peaks.__getitem__, budget, 16)
    assert n == want
    assert seen[n] <= budget
    assert all(seen[k] > budget for k in seen if k > n)


def test_train_phase_reduced():
    out = chip_smoke.train_phase([__import__("jax").devices()[0]],
                                 reduced_config("llama3.2-1b"), seq=64,
                                 batch=2, steps=3, budget=2**30,
                                 log=lambda s: None)
    assert out["ok"], out["checks"]
    assert out["layers_kept"] == out["layers_total"] == 2
    assert out["dp_mode"] == "zero1" and out["transport"] == "ring_hier"
    assert len(out["losses"]) == 3 and len(out["step_s"]) == 2


def test_serve_phase_reduced():
    out = chip_smoke.serve_phase([__import__("jax").devices()[0]],
                                 reduced_config("llama3.2-1b"),
                                 n_requests=10, prompt_max=8, out_min=2,
                                 out_max=4, max_seq_len=256)
    assert out["ok"], (out["checks"], out["logit_errors"], out["logit_tol"])
    # the kernel tiles the paged KV (L = 256, block 128): interpret mode
    # runs the kernel, not the jnp fallback
    assert out["kv"]["kernel_kv_len"] % 128 == 0
    assert out["generated_tokens"] == sum(out["decode_lens"])
    assert out["logit_max_abs_err"] <= out["logit_tol"]


CROSS_CHIP_SCRIPT = r"""
import json, sys
sys.path.insert(0, REPO)
import jax
import chip_smoke
from repro.configs import reduced_config

devs = jax.devices()
dp = chip_smoke.dp_phase(devs[:4], reduced_config("llama3.2-1b"), layers=2,
                         seq=64, log=lambda s: None)
halo = chip_smoke.halo_phase(devs[:4], local=8, comps=4)
print(json.dumps({"dp": dp, "halo": halo}, default=float))
"""


def test_cross_chip_phases_on_host_devices():
    import json

    out = run_distributed(f"REPO = {REPO!r}\n" + CROSS_CHIP_SCRIPT,
                          n_devices=4)
    res = json.loads(out.strip().splitlines()[-1])
    dp, halo = res["dp"], res["halo"]
    assert dp["ok"], dp["checks"]
    assert dp["runs"]["ring_hier"]["dp_mode"] == "zero1"
    assert dp["runs"]["psum"]["dp_mode"] == "replicated"
    assert dp["checks"]["lr0_zero_lr1_positive"]
    assert halo["ok"], halo["checks"]
    assert halo["decomposition"] == "2x2x1"


def _swap_segments(bucketer, plan, tree, bucket, world=4):
    """``tree`` with ranks 1 and 2's segments of one bucket exchanged: a
    collective that delivers a reduce-scatter or all-gather segment to the
    wrong rank."""
    import jax
    import numpy as np

    flat = [np.array(b) for b in bucketer.bucketize(tree, plan)[0]]
    seg = flat[bucket].size // world
    b = flat[bucket]
    b[seg:2 * seg], b[2 * seg:3 * seg] = (b[2 * seg:3 * seg].copy(),
                                          b[seg:2 * seg].copy())
    return jax.device_get(bucketer.debucketize(flat, plan))


@pytest.mark.parametrize("bucket", [0, -1])
def test_dp_checks_catch_a_misrouted_bucket_segment(bucket):
    import jax
    import numpy as np

    from repro.core.bucketing import GradientBucketer
    from repro.models import build_model
    from repro.optim import OptimConfig

    model = build_model(reduced_config("llama3.2-1b"))
    p0 = jax.device_get(model.init(jax.random.key(0)))
    bucketer = GradientBucketer(bucket_bytes=64 * 2**10)
    plan = bucketer.plan(p0)
    assert plan.n_buckets > 2
    rng = np.random.default_rng(0)
    mu = jax.tree.map(
        lambda p: 1e-3 * rng.standard_normal(p.shape).astype(np.float32), p0)
    nu = jax.tree.map(lambda m: 0.5 * m * m, mu)
    optim, lr = OptimConfig(), 3e-4
    f32 = np.float32
    c1, c2 = f32(1 - optim.b1 ** 2), f32(1 - optim.b2 ** 2)
    decay = f32(1) - f32(lr) * f32(optim.weight_decay)
    p1 = jax.tree.map(lambda p, m, v: p * decay - f32(lr) * (
        (m / c1) / (np.sqrt(v / c2) + f32(optim.eps))), p0, mu, nu)

    assert chip_smoke.moment_mismatch(mu, mu)["l2"] == 0
    assert chip_smoke.adamw_mismatch(p0, p1, mu, nu, lr=lr, t=2,
                                     optim=optim) <= chip_smoke.UPDATE_ATOL_LR
    # reduce-scatter side: the moments of a misrouted segment
    bad = chip_smoke.moment_mismatch(
        _swap_segments(bucketer, plan, mu, bucket), mu)
    assert bad["l2"] > 0.1 and bad["max"] > 0.1
    # all-gather side: the updates of a misrouted segment
    assert chip_smoke.adamw_mismatch(
        p0, _swap_segments(bucketer, plan, p1, bucket), mu, nu, lr=lr, t=2,
        optim=optim) > 1e2 * chip_smoke.UPDATE_ATOL_LR

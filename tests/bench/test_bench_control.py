"""The controls of the correctness check, at a size a test run can hold,
read through each driver's ``control_readings`` (what ``bench/control.py``
calls): the plain reference computed with fp8 matrix products, put in the
program's place, fails the cell's own limits (training: by at least one
of its numbers; serving: the widest logit gap), and so do the faults
planted in the reference."""

import functools

import jax
import pytest

from tiny_cells import SEED, parts

from bench import harness  # noqa: E402


@functools.lru_cache(maxsize=None)
def _readings(workload, seconds=0.0):
    cell, cfg, mix, limits, _, _ = parts(workload)
    drv = harness.driver_class(mix)(cell, cfg, mix, jax.devices()[:1], SEED,
                                    log=lambda *a: None)
    return drv.control_readings(seconds), limits


def _fails(reading, limits):
    return any(reading[k] > v for k, v in limits.items())


@pytest.mark.parametrize("case", ["control", "half_batch"])
def test_train_control_and_faults_fail_the_limits(case):
    got, limits = _readings("minicpm-2b.train.1chip")
    assert not _fails(got["program"], limits), got["program"]
    assert _fails(got[case], limits), got[case]


def test_serve_control_and_altered_token_fail_the_limit():
    got, limits = _readings("phi3-medium-14b.serve.decode_heavy", 1.0)
    limit = limits["logit_gap"]
    gap, ctrl = got["program"]["logit_gap"], got["control"]["logit_gap"]
    assert gap <= limit < ctrl, (gap, ctrl, limit)
    assert got["altered_token"]["logit_gap"] > limit
    assert got["checked_tokens"] > 0

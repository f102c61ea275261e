"""The training driver at a tiny size on the CPU, through the harness's
own path: a sound run is correct, and each fault a training cell can have
makes ``correct`` come out false (with the cell's own limits)."""

import math

import pytest

from tiny_cells import run

W = "minicpm-2b.train.1chip"


def test_sound_run_is_correct_and_reports_its_metrics():
    out = run(W)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert out["metrics"]["setup_s"]["value"] > 0
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == {"loss_gap", "grad_gap", "update_gap"}


def test_traced_run_reports_per_layer_metrics():
    out = run(W, traced=True)
    assert out["correct"]
    # peaks are only known for a chip: the shares of a peak stay silent
    assert "idle_share.train" in out["metrics"]
    assert "mfu.train" not in out["metrics"]
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
    assert out["breakdown"]["device_ops"]


@pytest.mark.parametrize("fault", ["stale_state", "half_batch"])
def test_faults_are_not_correct(fault):
    out = run(W, fault=fault)
    assert not out["correct"], out["checks"]
    if fault == "stale_state":
        assert math.isclose(out["checks"]["update_gap"]["value"], 1.0)

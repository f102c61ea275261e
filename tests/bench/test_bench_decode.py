"""The serving driver at a tiny size on the CPU (the flash-decode kernel
in interpret mode), through the harness's own path."""

from tiny_cells import run

W = "phi3-medium-14b.serve.decode_heavy"


def test_sound_run_is_correct_and_reports_its_metrics():
    out = run(W, seconds=1.0)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1
    assert set(out["metrics"]) == {"serve_tokens_per_s", "tbt_p95_ms",
                                   "setup_s"}
    assert list(out["checks"]) == ["logit_gap"]


def test_traced_run_reports_per_layer_metrics():
    out = run(W, seconds=1.0, traced=True)
    assert out["correct"]
    assert {"decode_step_ms.serve", "idle_share.serve"} <= set(out["metrics"])
    assert out["breakdown"]["idle_gaps"]


def test_altered_token_is_not_correct():
    out = run(W, seconds=1.0, fault="altered_token")
    assert not out["correct"], out["checks"]


def test_requests_in_flight_are_checked():
    """A window that finishes no request still checks the tokens served so
    far, of requests still in flight at its close."""
    from tiny_cells import SERVE
    long_outputs = {"dist": "lognormal", "median": 200, "sigma": 0.1,
                    "min": 180, "max": 220}
    over = {"cfg": SERVE["cfg"],
            "mix": dict(SERVE["mix"], output=long_outputs)}
    out = run(W, seconds=0.3, overrides=over)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1

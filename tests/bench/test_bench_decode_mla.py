"""The latent attention and expert serving cell at a tiny Moonlight shape on
the CPU (the ``mla_decode`` kernel in interpret mode), through the
harness's own path: the configuration's driver, weights, reference and
check, with the tiny sizes laid over its files."""

import time

import pytest

from tiny_cells import SEED, parts, run

W = "moonlight-16b-a3b.serve.decode_long"
# float32 throughout at this width: at d_model 64 one expert choice that a
# bf16 rounding flips moves the logits by O(1), which would drown what the
# check compares; the cell itself runs in bfloat16 at the published width
TINY = {"cfg": {"precision": {"params": "float32", "compute": "float32",
                              "kv_cache": "float32"},
                "hidden_size": 64, "num_attention_heads": 4,
                "num_key_value_heads": 4, "qk_nope_head_dim": 16,
                "qk_rope_head_dim": 16, "v_head_dim": 16, "kv_lora_rank": 32,
                "intermediate_size": 128, "moe_intermediate_size": 32,
                "n_routed_experts": 8, "num_experts_per_tok": 2,
                "num_experts_held": 4, "n_shared_experts": 1,
                "num_hidden_layers": 3, "vocab_size": 512},
        "mix": {"slots": 4, "page_tokens": 8, "warm_steps": 4,
                "trace_seconds": 0.5,
                "prompt": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                           "min": 4, "max": 16},
                "output": {"dist": "lognormal", "median": 16, "sigma": 0.5,
                           "min": 8, "max": 32}}}
NEW_METRICS = {"mla_decode_roofline", "moe_experts_ms.mla_serve",
               "mfu.mla_serve", "idle_share.mla_serve"}


def test_sound_run_is_correct():
    out = run(W, seconds=1.0, overrides=TINY)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1
    assert set(out["metrics"]) == {"serve_tokens_per_s", "tbt_p95_ms",
                                   "setup_s"}
    assert list(out["checks"]) == ["logit_gap", "logit_gap_mean"]


def test_altered_token_is_not_correct():
    out = run(W, seconds=1.0, fault="altered_token", overrides=TINY)
    assert not out["correct"], out["checks"]


def test_traced_run_reports_the_new_metrics(monkeypatch):
    """With the v5e's peaks standing in for the CPU's (the readers need
    peaks; the numbers of a CPU run mean nothing), a traced run reports
    every per-layer metric of the cell, the kernel found in interpret
    mode through its scope."""
    from bench import harness, peaks

    monkeypatch.setattr(harness, "devices_for",
                        lambda chips, require_chip: __import__("jax").devices())
    monkeypatch.setattr(peaks, "peaks_for", lambda kind: peaks.V5E)
    out = harness.run_cell(W, SEED, 0.5, True, t_start=time.time(),
                           require_chip=True, parts=parts(W, TINY),
                           log=lambda *a: None)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == NEW_METRICS
    for name, m in out["metrics"].items():
        assert m["value"] > 0, (name, m)


@pytest.mark.parametrize("key, value", [("scoring_func", "softmax"),
                                        ("q_lora_rank", 1536)])
def test_a_configuration_the_program_does_not_compute_is_refused(key, value):
    from bench.drivers.decode_mla import program_model

    cfg = parts(W, TINY)[1]
    with pytest.raises(ValueError, match=key):
        program_model(dict(cfg, **{key: value}))

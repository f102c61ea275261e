"""Per-architecture smoke tests: reduced same-family config, one forward +
train grad + one decode step on CPU; output shapes and finiteness asserted.
The FULL configs are exercised only via the AOT dry-run (no allocation)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SHAPES, applicable_shapes, get_config, list_archs, \
    reduced_config, sub_quadratic
from repro.models import build_model

ARCHS = list_archs()


@pytest.mark.parametrize("arch", ARCHS)
def test_reduced_train_and_decode(arch, rng):
    cfg = reduced_config(arch)
    m = build_model(cfg)
    params = m.init(jax.random.key(0))
    B, S = 2, 32
    batch = {"tokens": jnp.asarray(rng.randint(0, 500, (B, S)), jnp.int32),
             "labels": jnp.asarray(rng.randint(0, 500, (B, S)), jnp.int32)}
    if cfg.family == "encdec":
        batch["frames"] = jnp.asarray(
            rng.randn(B, cfg.enc_seq, cfg.d_model).astype(np.float32)) * 0.1
    if cfg.frontend == "vision_stub":
        batch["extra_embeds"] = jnp.asarray(
            rng.randn(B, cfg.frontend_seq, cfg.d_model).astype(np.float32)) * 0.1

    loss, grads = jax.jit(jax.value_and_grad(
        lambda p, b: m.loss_fn(p, b)))(params, batch)
    assert np.isfinite(float(loss)), f"{arch}: non-finite loss"
    gn = sum(float(jnp.sum(jnp.square(g.astype(jnp.float32))))
             for g in jax.tree.leaves(grads))
    assert np.isfinite(gn) and gn > 0, f"{arch}: bad grads"

    state = m.init_decode_state(B, 64, params=params,
                                frames=batch.get("frames"))
    logits, state2 = jax.jit(
        lambda p, t, s, pos: m.decode_step(p, t, s, pos, seq_len=64))(
        params, jnp.ones((B,), jnp.int32), state, jnp.asarray(3))
    assert logits.shape == (B, m.cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all()


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_registered(arch):
    cfg = get_config(arch)
    assert cfg.name == arch
    assert cfg.num_layers > 0 and cfg.d_model > 0 and cfg.vocab_size > 0
    shapes = applicable_shapes(cfg)
    assert "train_4k" in shapes and "decode_32k" in shapes
    # long_500k only for sub-quadratic archs (DESIGN.md §Arch-applicability)
    assert ("long_500k" in shapes) == sub_quadratic(cfg)


def test_assigned_cell_count():
    """44 assigned cells = 37 runnable + 7 documented long_500k skips."""
    total = sum(4 for _ in ARCHS)
    runnable = sum(len(applicable_shapes(get_config(a))) for a in ARCHS)
    assert total == 44
    assert runnable == 37


def test_arch_exact_hyperparams():
    spot = {
        "llava-next-34b": dict(num_layers=60, d_model=7168, d_ff=20480),
        "phi3-medium-14b": dict(num_layers=40, d_model=5120, d_ff=17920),
        "mixtral-8x7b": dict(num_layers=32, d_model=4096),
        "falcon-mamba-7b": dict(num_layers=64, d_model=4096, d_ff=0),
        "whisper-base": dict(num_layers=6, d_model=512, d_ff=2048),
    }
    for arch, want in spot.items():
        cfg = get_config(arch)
        for k, v in want.items():
            assert getattr(cfg, k) == v, (arch, k)
    assert get_config("mixtral-8x7b").moe.num_experts == 8
    assert get_config("llama4-maverick-400b-a17b").moe.num_experts == 128
    assert get_config("hymba-1.5b").attn.num_kv_heads == 5
    assert get_config("qwen2-7b").attn.qkv_bias is True
    moon = get_config("moonlight-16b-a3b")
    assert (moon.attn.kv_lora_rank, moon.attn.qk_rope_head_dim,
            moon.moe.num_experts, moon.moe.top_k, moon.first_k_dense) == \
        (512, 64, 64, 6, 1)


def test_param_counts_in_range():
    """Total params should land near the published sizes (padding included)."""
    expect = {
        "llama3.2-1b": (1.0e9, 1.8e9),
        "qwen2-7b": (7.0e9, 8.5e9),
        "phi3-medium-14b": (13e9, 15.5e9),
        "mixtral-8x7b": (45e9, 50e9),
        "falcon-mamba-7b": (6.5e9, 8.5e9),
        "llava-next-34b": (33e9, 37e9),
        "llama4-maverick-400b-a17b": (370e9, 430e9),
        "minicpm-2b": (2.2e9, 3.3e9),
        "hymba-1.5b": (1.3e9, 2.1e9),
        "whisper-base": (0.05e9, 0.15e9),
        "moonlight-16b-a3b": (15.5e9, 16.5e9),
    }
    for arch, (lo, hi) in expect.items():
        n = build_model(get_config(arch)).param_count()
        assert lo <= n <= hi, f"{arch}: {n/1e9:.2f}B not in [{lo/1e9},{hi/1e9}]B"

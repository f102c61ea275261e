"""Multi-head latent attention and the held-expert layer against the plain
reference (``bench/reference/mla_moe_decoder.py``), at a tiny Moonlight
shape on the CPU: d_model 64, 4 heads, latent rank 32, RoPE 16, no-RoPE 16,
values 16, 8 routed experts top 2 of which 4 are held, one shared expert,
layer 0 dense.  Float32 throughout, so that the program and the reference
differ only by the order of float32 sums."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.reference import mla_moe_decoder as ref  # noqa: E402
from repro.configs import reduced_config  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.models import mla as mla_mod  # noqa: E402
from repro.models import moe as moe_mod  # noqa: E402
from repro.models.common import glu_mlp  # noqa: E402

# float32 sums over at most 40 positions and 3 layers in another order, at
# logits of magnitude ~4: measured below 1e-5
TOL = 1e-4
T = 20


def _cfg(held=4, first=0):
    base = reduced_config("moonlight-16b-a3b")
    moe = dataclasses.replace(base.moe, num_experts=8, top_k=2, expert_ff=32,
                              shared_expert_ff=32, experts_held=held,
                              first_expert=first)
    return base.with_(num_layers=3, moe=moe, dtype="float32")


def _dims(cfg):
    a, moe = cfg.attn, cfg.moe
    return {"d": cfg.d_model, "layers": cfg.num_layers,
            "vocab": cfg.vocab_size, "heads": a.num_heads,
            "nope": a.head_dim, "rope": a.qk_rope_head_dim,
            "v": a.v_head_dim, "lora": a.kv_lora_rank, "dense_ff": cfg.d_ff,
            "expert_ff": moe.expert_ff, "shared_ff": moe.shared_expert_ff,
            "experts": moe.num_experts,
            "held": moe_mod.held_count(moe), "first": moe.first_expert,
            "top_k": moe.top_k, "scaling": moe.routed_scaling,
            "dense_layers": cfg.first_k_dense, "eps": cfg.norm_eps,
            "theta": a.rope_theta, "tied": cfg.tie_embeddings}


def _params(model, seed=0):
    """Random weights, with a selection bias small and non-zero (so that
    the experts chosen and their weights follow different scores)."""
    p = model.init(jax.random.key(seed))
    for i, bp in enumerate(p["blocks"]):
        if "moe" in bp:
            bp["moe"]["score_bias"] = 0.05 * jax.random.normal(
                jax.random.key(100 + i), bp["moe"]["score_bias"].shape)
    return p


@pytest.fixture(scope="module")
def tiny():
    model = build_model(_cfg())
    params = _params(model)
    toks = np.random.RandomState(1).randint(0, 500, (2, T)).astype(np.int32)
    want = np.stack([np.asarray(ref.stream_logits(params, t,
                                                  _dims(model.cfg)))
                     for t in toks])
    return model, params, toks, want


@pytest.mark.parametrize("attn_impl", ["ref", "kernel"])
def test_paged_decode_matches_the_reference(tiny, attn_impl):
    """Token by token through the paged engine (latent pages of 8 tokens,
    two slots admitted three steps apart), the logits at every position
    agree with the reference's full forward pass."""
    from repro.serve import PagedDecodeEngine, plan_kv_arena

    model, params, toks, want = tiny
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    plan = plan_kv_arena(model.cfg, mesh, page_tokens=8, page_bytes=4096,
                         max_seqs=4, max_seq_len=32, cache_dtype=jnp.float32)
    assert plan.kind == "latent"
    eng = PagedDecodeEngine(model, mesh, plan, attn_impl=attn_impl,
                            interpret=True)
    slots, lag = (1, 3), 3
    got = np.zeros_like(want)
    for step in range(T + lag):
        for j, s in enumerate(slots):
            if step == j * lag:
                eng.admit(s)
        tok = np.zeros((4,), np.int32)
        pos = [step - j * lag for j in range(2)]
        for j, s in enumerate(slots):
            if 0 <= pos[j] < T:
                tok[s] = toks[j, pos[j]]
        out = np.asarray(eng.decode(params, tok))
        for j, s in enumerate(slots):
            if 0 <= pos[j] < T:
                got[j, pos[j]] = out[s, :want.shape[-1]]
    assert np.abs(got - want).max() < TOL


def test_absorbed_decode_matches_naive_attention():
    """MLA decoded a token at a time in the absorbed form (latent cache,
    ``W_UK`` before the scores, ``W_UV`` after) equals the naive full
    sequence form (keys and values expanded per head)."""
    cfg = _cfg().attn
    p = mla_mod.mla_init(jax.random.key(3), cfg, 64)
    x = jax.random.normal(jax.random.key(4), (2, 12, 64))
    full = mla_mod.mla_apply(p, x, cfg, eps=1e-5, compute_dtype=jnp.float32)
    cache = mla_mod.init_cache(cfg, 2, 16, dtype=jnp.float32)
    steps = []
    for t in range(12):
        y, cache = mla_mod.mla_decode(p, x[:, t:t + 1], cfg, cache,
                                      pos=jnp.asarray(t), eps=1e-5,
                                      compute_dtype=jnp.float32)
        steps.append(y)
    got = jnp.concatenate(steps, axis=1)
    np.testing.assert_allclose(got, full, atol=1e-5, rtol=1e-5)


def test_fp8_control_fails_the_tolerance(tiny):
    """The reference in fp8 matrix products (the control) is farther from
    the float32 reference than the tolerance the program meets."""
    model, params, toks, want = tiny
    ctrl = np.asarray(ref.stream_logits(params, toks[0], _dims(model.cfg),
                                        "fp8"))
    assert np.abs(ctrl - want[0]).max() > 10 * TOL


def _layer(held, first, p_uncut):
    """The expert layer of a holder of ``held`` experts from ``first``,
    its expert weights sliced from the uncut layer's."""
    cfg = _cfg(held, first).moe
    p = dict(p_uncut)
    for k in ("w_gate", "w_up", "w_down"):
        p[k] = p_uncut[k][first:first + held]
    return cfg, p


def test_holders_shares_add_up_to_the_uncut_layer():
    """Two holders of 4 experts each: their outputs, the shared expert
    counted once, add up to what the uncut reference gives for the whole
    layer (all 8 experts held)."""
    model = build_model(_cfg(held=8))
    p = _params(model)["blocks"][1]["moe"]
    x = jax.random.normal(jax.random.key(5), (3, 7, 64))
    parts = [moe_mod.moe_apply(q, x, c, "silu", ctx=None,
                               compute_dtype=jnp.float32)[0]
             for c, q in (_layer(4, 0, p), _layer(4, 4, p))]
    shared = glu_mlp(p["shared"], x, "silu", jnp.float32)
    got = parts[0] + parts[1] - shared
    m = _dims(model.cfg)
    want = ref.experts(p, x.reshape(-1, 64), m, "float32").reshape(x.shape)
    assert np.abs(np.asarray(got - want)).max() < TOL
    # and each share alone is not the whole layer
    assert np.abs(np.asarray(parts[0] - want)).max() > 100 * TOL


def test_every_token_on_one_held_expert_drops_nothing():
    """Every one of 64 tokens chooses held expert 2 (and expert 6, held
    elsewhere): the held layer computes expert 2's weighted output for
    every token, where a capacity of 1.25 x the even share would have kept
    24 of them."""
    model = build_model(_cfg(held=4))
    p = dict(_params(model)["blocks"][1]["moe"])
    p["router"] = {"w": jnp.zeros_like(p["router"]["w"])}
    p["score_bias"] = jnp.zeros((8,)).at[2].set(1.0).at[6].set(0.5)
    x = jax.random.normal(jax.random.key(6), (1, 64, 64))
    cfg = model.cfg.moe
    y, _, drop = moe_mod.moe_apply(p, x, cfg, "silu", ctx=None,
                                   compute_dtype=jnp.float32)

    def expert(e, h):
        return (jax.nn.silu(h @ p["w_gate"][e]) * (h @ p["w_up"][e])) \
            @ p["w_down"][e]

    # sigmoid(0) = 0.5 for every expert: each chosen weighs 0.5 / 1.0 * 2.446
    want = (cfg.routed_scaling / 2 * expert(2, x)
            + glu_mlp(p["shared"], x, "silu", jnp.float32))
    np.testing.assert_allclose(y, want, atol=1e-4, rtol=1e-4)
    assert float(drop) == 0.0
    assert moe_mod.capacity(64, cfg) == 24


def test_engine_counts_latent_pages_and_held_expert_choices(tiny):
    """Under a live ``Obs`` the step's latent pages count in
    ``kv_blocks_read`` / ``kv_blocks_total``, and its expert choices in
    ``moe_assignments`` and, on held experts, ``moe_held_assignments``
    (read two steps late, flushed when the sink is swapped); under
    ``NULL_OBS`` nothing is held back."""
    from repro.obs import NULL_OBS, ObsConfig, make_obs
    from repro.serve import PagedDecodeEngine, plan_kv_arena

    model, params, toks, _ = tiny
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    plan = plan_kv_arena(model.cfg, mesh, page_tokens=8, page_bytes=4096,
                         max_seqs=4, max_seq_len=32)
    eng = PagedDecodeEngine(model, mesh, plan, attn_impl="ref")
    eng.admit(0)
    eng.admit(2)
    eng.decode(params, np.zeros((4,), np.int32))
    assert eng._routing == []             # NULL_OBS: nothing held back
    obs = make_obs(ObsConfig(run_dir=None))
    eng.obs = obs
    for t in range(10):
        eng.decode(params, np.full((4,), t, np.int32))
    eng.obs = NULL_OBS
    bus = obs.bus
    # 2 live slots x 2 expert layers x top 2, each of 10 steps
    assert bus.counter_total("moe_assignments") == 10 * 2 * 2 * 2
    held = bus.counter_total("moe_held_assignments")
    assert 0 < held < bus.counter_total("moe_assignments")
    # 3 layers x (blocks 0 and, from position 8, 1) of 2 slots
    assert bus.counter_total("kv_blocks_total") == 10 * plan.max_seqs \
        * plan.max_blocks * plan.n_layers
    assert bus.counter_total("kv_blocks_read") == 3 * 2 * (10 + 3)


@pytest.mark.parametrize("arch, met", [("falcon-mamba-7b", "family='ssm'"),
                                       ("mixtral-8x7b", "local attention")])
def test_kv_plan_refusal_names_the_kind_it_met(arch, met):
    from repro.serve import plan_kv_arena

    with pytest.raises(NotImplementedError, match=met):
        plan_kv_arena(reduced_config(arch), page_tokens=8)

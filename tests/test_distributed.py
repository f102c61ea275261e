"""Distributed correctness on 8 fake host devices (fresh subprocesses so the
main pytest process keeps its single real device)."""

import pytest

from conftest import run_distributed

RING_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, PartitionSpec as P
from repro.core import ring
from repro.core.ring import RingConfig

mesh = jax.make_mesh((2, 4), ("pod", "data"), axis_types=(AxisType.Auto,) * 2)
L = 2*4*2*4*512*2
x = np.random.RandomState(0).randn(8, L).astype(np.float32)
want = x.sum(0)

def run(fn, cfg, axes):
    g = jax.jit(jax.shard_map(lambda xl: fn(xl.reshape(-1), axes, cfg),
     mesh=mesh, in_specs=P(("pod","data")), out_specs=P(), check_vma=False))
    return np.asarray(g(x.reshape(-1)))

for cfg in [RingConfig(chunks=1, bidirectional=False),
            RingConfig(chunks=2, bidirectional=True),
            RingConfig(chunks=4, bidirectional=True)]:
    out = run(ring.hierarchical_all_reduce, cfg, ("data","pod"))
    assert np.abs(out - want).max() < 1e-4, cfg
    out = run(ring.flat_all_reduce, cfg, ("data","pod"))
    assert np.abs(out - want).max() < 1e-4, cfg

# lossy wire configs: bounded relative error
for cfg, tol in [(RingConfig(chunks=2, bidirectional=True, wire_dtype="bfloat16"), 0.03),
                 (RingConfig(chunks=2, bidirectional=True, codec="int8", codec_block=256), 0.05)]:
    out = run(ring.hierarchical_all_reduce, cfg, ("data","pod"))
    rel = np.abs(out - want).max() / np.abs(want).max()
    assert rel < tol, (cfg, rel)

# RS/AG roundtrip == AR
cfg = RingConfig(chunks=2, bidirectional=True)
def rsag(xl):
    s = ring.ring_reduce_scatter(xl.reshape(-1), "data", cfg)
    return ring.ring_all_gather(s, "data", cfg)
g = jax.jit(jax.shard_map(rsag, mesh=mesh, in_specs=P(("pod","data")),
 out_specs=P(("pod","data")), check_vma=False))
out = np.asarray(g(x.reshape(-1))).reshape(2, 4, L)
per_pod = x.reshape(2,4,L).sum(1)
for p in range(2):
    for d in range(4):
        assert np.abs(out[p,d] - per_pod[p]).max() < 1e-4
print("RING_OK")
"""

REDUCER_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, PartitionSpec as P, NamedSharding
from repro.core.reducer import GradientReducer, ReduceConfig

mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(AxisType.Auto,) * 3)
rng = np.random.RandomState(1)
grads = {"w": jnp.asarray(rng.randn(16, 256).astype(np.float32)),
         "b": jnp.asarray(rng.randn(256).astype(np.float32)),
         "emb": jnp.asarray(rng.randn(1000, 64).astype(np.float32))}
specs = {"w": P(None, "model"), "b": P(), "emb": P("model", None)}
sh = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                  is_leaf=lambda x: isinstance(x, P))
grads = jax.tree.map(lambda g, s: jax.device_put(g, s), grads, sh)

for policy in ["fused_ring_hierarchical", "fused_ring", "native_psum",
               "native_psum_fused", "baidu_original"]:
    red = GradientReducer(mesh, ReduceConfig(policy=policy, data_axes=("pod","data"), chunks=2))
    def mk(x):
        i = jax.lax.axis_index("pod")*2 + jax.lax.axis_index("data")
        return jax.tree.map(lambda t: t*(1.0+i), x)
    gv = jax.jit(jax.shard_map(mk, mesh=mesh, in_specs=(specs,),
                               out_specs=specs, check_vma=False))(grads)
    out = jax.jit(lambda g: red.reduce(g, specs)[0])(gv)
    scale = np.mean([1.0+i for i in range(4)])
    for k in grads:
        err = float(jnp.max(jnp.abs(out[k] - grads[k]*scale)))
        assert err < 1e-4, (policy, k, err)
print("REDUCER_OK")
"""

HALO_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, PartitionSpec as P
from repro.core.halo import HaloSpec, halo_exchange

mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,) * 1)
Y = jnp.arange(64, dtype=jnp.float32).reshape(64, 1)
for sched in ["concurrent", "sequential", "chunked"]:
    def hx(xl, s=sched):
        h = halo_exchange(xl, [HaloSpec("data", 0)], schedule=s, chunks=1)
        return jnp.concatenate([h[("data","-")], xl, h[("data","+")]], 0)
    g = jax.jit(jax.shard_map(hx, mesh=mesh, in_specs=P("data"),
                              out_specs=P("data"), check_vma=False))
    out = np.asarray(g(Y)).reshape(8, 10)
    ys = np.asarray(Y).reshape(8, 8)
    for r in range(8):
        exp = np.concatenate([[ys[(r-1)%8,-1]], ys[r], [ys[(r+1)%8,0]]])
        assert np.array_equal(out[r], exp), (sched, r)
print("HALO_OK")
"""

DPMODES_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, PartitionSpec as P
from repro.configs import reduced_config
from repro.models import build_model
from repro.runtime.train_step import TrainStepConfig, build_train_step, init_train_state
from repro.core.reducer import ReduceConfig
from repro.optim import adamw_tree_update, init_opt_state, OptimConfig, make_schedule
from repro.optim.adamw import clip_factor

mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(AxisType.Auto,) * 3)
cfg = reduced_config("llama3.2-1b")
m = build_model(cfg)
B, S = 8, 32
rng = np.random.RandomState(0)
batch = {"tokens": jnp.asarray(rng.randint(0, 500, (B, S)), jnp.int32),
         "labels": jnp.asarray(rng.randint(0, 500, (B, S)), jnp.int32)}
bspecs = {"tokens": P(("pod","data"), None), "labels": P(("pod","data"), None)}

ocfg = OptimConfig()
params = m.init(jax.random.key(7))
opt = init_opt_state(params)
sched = make_schedule(ocfg.schedule, base_lr=ocfg.base_lr, warmup=ocfg.warmup,
                      total=ocfg.total_steps)
@jax.jit
def ref_step(params, opt, step):
    loss, g = jax.value_and_grad(lambda p: m.loss_fn(p, batch))(params)
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree.leaves(g)))
    g = jax.tree.map(lambda x: x * clip_factor(gn, ocfg.clip_norm), g)
    p2, opt2 = adamw_tree_update(params, g, opt, step, sched(step), ocfg)
    return p2, opt2, loss
ref = []
st = jnp.zeros((), jnp.int32)
for i in range(3):
    params, opt, loss = ref_step(params, opt, st); st = st + 1
    ref.append(float(loss))

for mode, tol in [("replicated", 5e-5), ("zero1", 5e-5), ("fsdp", 5e-4)]:
    tcfg = TrainStepConfig(dp_mode=mode,
                           reduce=ReduceConfig(policy="fused_ring_hierarchical", chunks=2),
                           microbatches=2)
    with mesh:
        state, _ = init_train_state(m, mesh, tcfg, key=jax.random.key(7))
        step = build_train_step(m, mesh, tcfg, bspecs)
        losses = []
        for i in range(3):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
    err = max(abs(a-b) for a, b in zip(ref, losses))
    assert err < tol, (mode, ref, losses)
    print(mode, "OK", err)
print("DPMODES_OK")
"""

SERVE_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, PartitionSpec as P, NamedSharding
from repro.configs import reduced_config, base
from repro.models import build_model
from repro.runtime.serve_step import build_decode_step, build_prefill
from repro.sharding import shardings_of

mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
cfg = reduced_config("llama3.2-1b")
m = build_model(cfg)
params = m.init(jax.random.key(0))
B, S = 8, 16384  # long cache -> seq-sharded kv path
shape = base.ShapeConfig("t", S, B, "decode")
step, pspecs, sspecs = build_decode_step(m, mesh, shape)
with mesh:
    psh = shardings_of(pspecs, mesh)
    params_d = jax.jit(lambda p: p, out_shardings=psh)(params)
    state = jax.jit(lambda: m.abstract_decode_state(B, S) and None)  # noqa
    import repro.models.transformer as T
    state = T.init_decode_state(m.cfg, B, S)
    state = jax.jit(lambda s: s, out_shardings=shardings_of(sspecs, mesh))(state)
    # single-device reference via plain decode
    tok = jnp.arange(B, dtype=jnp.int32) % 100
    ref_state = T.init_decode_state(m.cfg, B, S)
    logits_ref, _ = m.decode_step(params, tok, ref_state, jnp.asarray(0), seq_len=S)
    logits, state = step(params_d, tok, state, jnp.asarray(0))
    err = float(jnp.max(jnp.abs(logits.astype(jnp.float32) - logits_ref.astype(jnp.float32))))
    assert err < 2e-2, err
print("SERVE_OK", err)
"""


@pytest.mark.slow
def test_ring_collectives_distributed():
    assert "RING_OK" in run_distributed(RING_SCRIPT)


@pytest.mark.slow
def test_reducer_policies_distributed():
    assert "REDUCER_OK" in run_distributed(REDUCER_SCRIPT)


@pytest.mark.slow
def test_halo_exchange_distributed():
    assert "HALO_OK" in run_distributed(HALO_SCRIPT)


@pytest.mark.slow
def test_dp_modes_match_single_device():
    assert "DPMODES_OK" in run_distributed(DPMODES_SCRIPT)


@pytest.mark.slow
def test_serve_decode_seq_sharded_kv():
    assert "SERVE_OK" in run_distributed(SERVE_SCRIPT)


EP_BITWISE_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, PartitionSpec as P
from repro.configs.base import MoEConfig
from repro.models import moe as moe_mod
from repro.models.parallel import SINGLE
from repro.runtime.train_step import TrainStepConfig, make_ctx

mesh = jax.make_mesh((2,), ("model",), axis_types=(AxisType.Auto,) * 1)
cfg = MoEConfig(num_experts=4, top_k=2, expert_ff=32, capacity_factor=2.0,
                parallelism="ep")
d, B, S = 16, 4, 8
p = moe_mod.moe_init(jax.random.key(0), cfg, d)
x = jnp.asarray(np.random.RandomState(1).randn(B, S, d).astype(np.float32))
w = jnp.asarray(np.random.RandomState(2).randn(B, S, d).astype(np.float32))

pspecs = {"router": {"w": P()}, "w_gate": P("model"), "w_up": P("model"),
          "w_down": P("model")}


def loss(pp, xx, ctx):
    y, aux, drop = moe_mod.moe_apply(pp, xx, cfg, "silu", ctx=ctx,
                                     compute_dtype=jnp.float32)
    return jnp.sum(y * w) + aux, (y, drop)


ref_fn = jax.jit(jax.value_and_grad(lambda pp, xx: loss(pp, xx, SINGLE),
                                    argnums=(0, 1), has_aux=True))
(ref_l, (ref_y, ref_drop)), (ref_gp, ref_gx) = ref_fn(p, x)

for transport in ("a2a", "ring", "psum"):
    ctx = make_ctx(mesh, TrainStepConfig(moe_transport=transport))

    def sharded(pp, xx):
        (l, (y, drop)), (gp, gx) = jax.value_and_grad(
            lambda a, b: loss(a, b, ctx), argnums=(0, 1), has_aux=True)(pp, xx)
        # expert-shard cotangents are local; replicated leaves need no psum
        # (fan_out's backward already summed the rank-partials)
        return l, y, drop, gp, gx

    fn = jax.jit(jax.shard_map(
        sharded, mesh=mesh, in_specs=(pspecs, P()),
        out_specs=(P(), P(), P(), pspecs, P()), check_vma=False))
    l, y, drop, gp, gx = fn(p, x)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(ref_y))
    np.testing.assert_array_equal(np.asarray(l), np.asarray(ref_l))
    np.testing.assert_array_equal(np.asarray(drop), np.asarray(ref_drop))
    np.testing.assert_array_equal(np.asarray(gx), np.asarray(ref_gx))
    for k2 in ("w_gate", "w_up", "w_down"):
        np.testing.assert_array_equal(np.asarray(gp[k2]),
                                      np.asarray(ref_gp[k2]))
    np.testing.assert_array_equal(np.asarray(gp["router"]["w"]),
                                  np.asarray(ref_gp["router"]["w"]))
    print(transport, "bitwise ok")
print("EP_BITWISE_OK")
"""

EP_TOL_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, PartitionSpec as P
from repro.configs.base import MoEConfig
from repro.models import moe as moe_mod
from repro.models.parallel import SINGLE
from repro.runtime.train_step import TrainStepConfig, make_ctx

mesh = jax.make_mesh((4,), ("model",), axis_types=(AxisType.Auto,) * 1)
d = 32

cases = [
    # (cfg, B, S)  — B=6 does not divide the axis: replicated-psum fallback
    (MoEConfig(num_experts=8, top_k=2, expert_ff=64, capacity_factor=1.5,
               parallelism="ep"), 8, 16),
    (MoEConfig(num_experts=8, top_k=1, expert_ff=64, capacity_factor=2.0,
               shared_expert_ff=64, parallelism="ep"), 8, 16),
    (MoEConfig(num_experts=8, top_k=2, expert_ff=64, capacity_factor=1.5,
               parallelism="ep"), 6, 16),
]

for ci, (cfg, B, S) in enumerate(cases):
    p = moe_mod.moe_init(jax.random.key(ci), cfg, d)
    x = jnp.asarray(np.random.RandomState(ci).randn(B, S, d)
                    .astype(np.float32)) * 0.5
    w = jnp.asarray(np.random.RandomState(100 + ci).randn(B, S, d)
                    .astype(np.float32))
    pspecs = {"router": {"w": P()}, "w_gate": P("model"),
              "w_up": P("model"), "w_down": P("model")}
    if cfg.shared_expert_ff:
        pspecs["shared"] = jax.tree.map(
            lambda _: P(), p["shared"],
            is_leaf=lambda l: hasattr(l, "shape"))

    def loss(pp, xx, ctx):
        y, aux, _ = moe_mod.moe_apply(pp, xx, cfg, "silu", ctx=ctx,
                                      compute_dtype=jnp.bfloat16)
        return jnp.sum(y.astype(jnp.float32) * w) + aux

    (ref_l, ref_gx) = jax.jit(jax.value_and_grad(
        lambda pp, xx: loss(pp, xx, SINGLE), argnums=1))(p, x)

    ctx = make_ctx(mesh, TrainStepConfig(moe_transport="a2a"))
    fn = jax.jit(jax.shard_map(
        lambda pp, xx: jax.value_and_grad(
            lambda a, b: loss(a, b, ctx), argnums=1)(pp, xx),
        mesh=mesh, in_specs=(pspecs, P()), out_specs=(P(), P()),
        check_vma=False))
    l, gx = fn(p, x)
    np.testing.assert_allclose(float(l), float(ref_l), rtol=2e-2)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(ref_gx),
                               rtol=5e-2, atol=5e-2)
    print("case", ci, "ok", float(l), float(ref_l))
print("EP_TOL_OK")
"""


def test_moe_ep_bitwise_matches_dense_replica():
    """2 ranks, fusion pinned off: the EP all-to-all path (every transport)
    reproduces the single-rank dense-replica MoE forward AND backward
    bitwise — same arithmetic, only the placement moved."""
    assert "EP_BITWISE_OK" in run_distributed(
        EP_BITWISE_SCRIPT, n_devices=2,
        extra_flags="--xla_disable_hlo_passes=fusion")


@pytest.mark.slow
def test_moe_ep_tolerance_4rank():
    """4 ranks, bf16 compute, fusion on: EP == dense replica to bf16
    tolerance, including the shared-expert arch and the b %% r != 0
    replicated-psum fallback."""
    assert "EP_TOL_OK" in run_distributed(EP_TOL_SCRIPT, n_devices=4)

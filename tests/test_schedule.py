"""Dependency-aware CommSchedule: construction invariants, the overlap
fraction the roofline consumes, schedule-driven train steps for every DP
mode x policy (equivalence vs accumulate_then_reduce), and the independence
of the streamed collectives in lowered HLO."""

import numpy as np
import pytest

from conftest import run_distributed

from repro.comm import (CommConfig, Communicator, SCHEDULE_POLICIES,
                        build_schedule)


# ---------------------------------------------------------------------------
# construction invariants
# ---------------------------------------------------------------------------

SIZES = (512, 128, 1024, 256, 256)


@pytest.mark.parametrize("policy", SCHEDULE_POLICIES)
@pytest.mark.parametrize("channels", [0, 1, 2, 4])
@pytest.mark.parametrize("m", [1, 3])
def test_every_bucket_issued_each_phase(policy, channels, m):
    s = build_schedule(policy, SIZES, microbatches=m, channels=channels)
    assert s.n_buckets == len(SIZES)
    phases = range(m) if policy != "accumulate_then_reduce" else [m - 1]
    for phase in phases:
        seen = sorted(b for slot in s.slots_for_phase(phase)
                      for b in slot.bucket_ids)
        assert seen == list(range(len(SIZES)))
    expected = len(SIZES) * (m if policy != "accumulate_then_reduce" else 1)
    assert s.n_collectives == expected
    if channels >= 1:
        assert s.n_channels == min(channels, len(SIZES))


def test_readiness_monotone_per_channel_and_in_range():
    for policy in SCHEDULE_POLICIES:
        s = build_schedule(policy, SIZES, microbatches=4, channels=2)
        by_channel = {}
        for slot in s.slots:
            assert 0.0 < slot.ready <= 1.0
            assert slot.ready >= by_channel.get(slot.channel, 0.0)
            by_channel[slot.channel] = slot.ready


def test_scheduled_issues_last_buckets_first():
    """Backward readiness order: the last layers' gradients (highest bucket
    index) issue first within each phase."""
    s = build_schedule("scheduled", SIZES, microbatches=2, channels=0)
    for phase in (0, 1):
        order = [b for slot in s.slots_for_phase(phase)
                 for b in slot.bucket_ids]
        assert order == sorted(order, reverse=True)


def test_overlap_fraction_ordering():
    acc = build_schedule("accumulate_then_reduce", SIZES, 4, 2)
    st = build_schedule("stream", SIZES, 4, 2)
    sc = build_schedule("scheduled", SIZES, 4, 2)
    assert acc.overlap_fraction == 0.0
    assert 0.0 < st.overlap_fraction < sc.overlap_fraction < 1.0
    # single microbatch: stream cannot overlap, scheduled still can
    assert build_schedule("stream", SIZES, 1, 2).overlap_fraction == 0.0
    assert build_schedule("scheduled", SIZES, 1, 2).overlap_fraction > 0.0


def test_describe_round_trips_and_elides():
    s = build_schedule("stream", SIZES, 2, 2)
    d = s.describe()
    assert d["policy"] == "stream" and d["n_collectives"] == s.n_collectives
    assert len(d["slots"]) == len(s.slots)
    assert "slots" not in s.describe(max_slots=3)
    assert s.describe(max_slots=3)["slots_elided"] == len(s.slots)


def test_unknown_policy_raises():
    with pytest.raises(ValueError, match="unknown schedule policy"):
        build_schedule("bogus", SIZES)


def test_train_step_config_schedule_policy():
    from repro.runtime.train_step import TrainStepConfig

    assert TrainStepConfig().schedule_policy == "accumulate_then_reduce"
    assert TrainStepConfig(schedule="stream").schedule_policy == "stream"
    assert TrainStepConfig(schedule="scheduled",
                           microbatches=3).schedule_policy == "scheduled"
    with pytest.raises(ValueError, match="unknown schedule policy"):
        TrainStepConfig(schedule="bogus").schedule_policy


def test_roofline_exposed_collective_bounds():
    from repro.launch.roofline import Roofline

    base = dict(flops_per_device=1e12, hbm_bytes_per_device=1e9,
                wire_bytes_per_device=1e9)
    for frac in (0.0, 0.3, 1.0):
        r = Roofline(**base, overlap_fraction=frac)
        assert 0.0 <= r.t_exposed_collective <= r.t_collective
        d = r.as_dict(8)
        assert d["t_exposed_collective_s"] <= d["t_collective_s"]
        assert d["overlap_fraction"] == frac
    assert Roofline(**base).t_exposed_collective == \
        Roofline(**base).t_collective


# ---------------------------------------------------------------------------
# reduce_scheduled validation (single device)
# ---------------------------------------------------------------------------


def _comm(transport="ring_hier", **kw):
    import jax
    from jax.sharding import AxisType

    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,) * 1)
    return Communicator(mesh, CommConfig(transport=transport,
                                         data_axes=("data",), **kw))


def test_reduce_scheduled_rejects_bad_op():
    comm = _comm()
    sched = build_schedule("stream", (128,), 1, 0)
    with pytest.raises(ValueError, match="op must be"):
        comm.reduce_scheduled(lambda p, b: (0.0, p), {}, {}, sched,
                              op="bogus")


def test_reduce_scheduled_rejects_rs_on_psum():
    comm = _comm(transport="psum")
    sched = build_schedule("stream", (128,), 1, 0)
    with pytest.raises(ValueError, match="reduce-scatter"):
        comm.reduce_scheduled(lambda p, b: (0.0, p), {}, {}, sched,
                              op="reduce_scatter")


def test_reduce_scheduled_detects_bucket_mismatch():
    import jax.numpy as jnp

    comm = _comm(bucket_bytes=4096)                   # cap = 1024 elems
    params = {f"w{i}": jnp.zeros((600,), jnp.float32)
              for i in range(3)}                      # -> 3 buckets
    sched = build_schedule("stream", (128,), 1, 0)    # wrong layout

    def grad_fn(p, _):
        return jnp.zeros(()), p

    with pytest.raises(ValueError, match="bucketizes into"):
        comm.reduce_scheduled(grad_fn, params, {"x": jnp.zeros((1, 1))},
                              sched)


# ---------------------------------------------------------------------------
# schedule equivalence + HLO independence (distributed subprocess, 1xN mesh)
# ---------------------------------------------------------------------------

EQUIV_SCRIPT = r"""
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P
from repro.configs import reduced_config
from repro.core.reducer import ReduceConfig
from repro.models import build_model
from repro.runtime.train_step import (TrainStepConfig, build_train_step,
                                      init_train_state)

mesh = jax.make_mesh((4, 1), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)   # 1xN data parallel
cfg = reduced_config("llama3.2-1b")
model = build_model(cfg)
B, S = 8, 32
rng = np.random.RandomState(0)
batch = {"tokens": jnp.asarray(rng.randint(0, 500, (B, S)), jnp.int32),
         "labels": jnp.asarray(rng.randint(0, 500, (B, S)), jnp.int32)}
bspecs = {"tokens": P("data", None), "labels": P("data", None)}

def run(mode, policy):
    tcfg = TrainStepConfig(
        dp_mode=mode,
        reduce=ReduceConfig(policy="fused_ring_hierarchical", chunks=2),
        microbatches=2, schedule=policy)
    with mesh:
        state, _ = init_train_state(model, mesh, tcfg, key=jax.random.key(7))
        step = build_train_step(model, mesh, tcfg, bspecs)
        metrics = None
        for _ in range(2):
            state, metrics = step(state, batch)
    return state, metrics

def flat(tree):
    return jax.tree.leaves(tree)

for mode in ("replicated", "zero1", "fsdp"):
    ref_state, ref_metrics = run(mode, "accumulate_then_reduce")
    for policy in ("stream", "scheduled"):
        st, mt = run(mode, policy)
        assert abs(float(mt["loss"] - ref_metrics["loss"])) < 1e-5, \
            (mode, policy)
        assert abs(float(mt["grad_norm"] - ref_metrics["grad_norm"])) < 1e-4, \
            (mode, policy, float(mt["grad_norm"]), float(ref_metrics["grad_norm"]))
        for a, b in zip(flat(st), flat(ref_state)):
            err = float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                        - b.astype(jnp.float32))))
            assert err < 5e-5, (mode, policy, a.shape, err)
        print(mode, policy, "equiv ok")
print("SCHED_EQUIV_OK")
"""

HLO_SCRIPT = r"""
import re
import jax, jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P
from repro.comm import CommConfig
from repro.configs import reduced_config
from repro.models import build_model
from repro.runtime.train_step import (TrainStepConfig, build_step_schedule,
                                      build_train_step, init_train_state)

mesh = jax.make_mesh((4, 1), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
cfg = reduced_config("llama3.2-1b")
model = build_model(cfg)
bspecs = {"tokens": P("data", None), "labels": P("data", None)}
batch_abs = {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32),
             "labels": jax.ShapeDtypeStruct((8, 32), jnp.int32)}

for policy in ("stream", "scheduled"):
    # psum transport: every bucket lowers to one all-reduce op; small
    # buckets force several, channels=0 leaves them independent
    tcfg = TrainStepConfig(
        dp_mode="replicated",
        comm=CommConfig(transport="psum", bucket_bytes=1 << 16, channels=0),
        microbatches=2, schedule=policy)
    with mesh:
        sched = build_step_schedule(model, mesh, tcfg)
        state_abs, _ = init_train_state(model, mesh, tcfg, abstract=True)
        step = build_train_step(model, mesh, tcfg, bspecs)
        txt = step.lower(state_abs, batch_abs).as_text()
    n_ar = len(re.findall(r"all[-_]reduce", txt))
    assert sched.n_buckets > 1, sched.n_buckets
    # the streamed schedule issues n_buckets independent collectives per
    # microbatch; all of them must survive into the lowered module
    assert n_ar >= sched.n_collectives >= sched.n_buckets, \
        (policy, n_ar, sched.n_collectives, sched.n_buckets)
    print(policy, "buckets", sched.n_buckets, "collectives in HLO", n_ar)
print("SCHED_HLO_OK")
"""


def test_schedule_collectives_survive_lowering():
    assert "SCHED_HLO_OK" in run_distributed(HLO_SCRIPT, n_devices=4)


@pytest.mark.slow
def test_dp_mode_x_policy_equivalence():
    assert "SCHED_EQUIV_OK" in run_distributed(EQUIV_SCRIPT, n_devices=4)

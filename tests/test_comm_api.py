"""The unified repro.comm Communicator API: registry semantics, channel
striping, capability validation, and numerical equivalence of every
registered transport against ``lax.psum`` on a 1-D mesh."""

import numpy as np
import pytest

from conftest import run_distributed

from repro.comm import (CommConfig, Communicator, POLICY_TO_TRANSPORT,
                        assign_channels, comm_config_from_policy,
                        get_transport, list_transports, transport_specs)
from repro.core.reducer import POLICIES


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_builtin_transports_registered():
    names = list_transports()
    for expected in ("a2a", "ring", "ring_hier", "psum"):
        assert expected in names
    assert "ring_compressed" not in names


def test_removed_ring_compressed_tombstone():
    with pytest.raises(ValueError, match="wire_codec='int8'"):
        get_transport("ring_compressed")


def test_get_transport_unknown_raises_with_menu():
    with pytest.raises(ValueError, match="unknown transport"):
        get_transport("definitely_not_a_transport")
    with pytest.raises(ValueError, match="ring_hier"):
        get_transport("definitely_not_a_transport")


def test_transport_specs_capabilities():
    specs = transport_specs()
    assert specs["ring"].supports_rs
    assert specs["ring_hier"].supports_rs
    assert not specs["psum"].supports_rs
    assert specs["ring_hier"].hierarchical
    assert not specs["ring"].hierarchical
    # all-to-all capability: native + rings + the honest psum fallback
    assert specs["a2a"].supports_a2a
    assert specs["ring"].supports_a2a
    assert specs["psum"].supports_a2a
    assert not specs["a2a"].supports_rs


def test_every_legacy_policy_maps_to_registered_transport():
    assert set(POLICY_TO_TRANSPORT) == set(POLICIES)
    for policy, (transport, _) in POLICY_TO_TRANSPORT.items():
        get_transport(transport)  # must not raise
        ccfg = comm_config_from_policy(policy)
        assert ccfg.transport == transport


def test_comm_config_from_policy_forced_overrides():
    ccfg = comm_config_from_policy("baidu_original", chunks=8,
                                   bidirectional=True)
    assert ccfg.chunks == 1 and ccfg.bidirectional is False
    assert comm_config_from_policy("native_psum").fuse is False
    with pytest.raises(ValueError, match="unknown policy"):
        comm_config_from_policy("nope")


# ---------------------------------------------------------------------------
# construction-time capability validation
# ---------------------------------------------------------------------------


def _mesh1():
    import jax
    from jax.sharding import AxisType

    return jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,) * 1)


def test_unknown_transport_fails_at_construction():
    with pytest.raises(ValueError, match="unknown transport"):
        Communicator(_mesh1(), CommConfig(transport="bogus",
                                          data_axes=("data",)))


def test_invalid_wire_dtype_fails_at_construction():
    with pytest.raises(ValueError, match="wire_dtype"):
        Communicator(_mesh1(), CommConfig(transport="psum",
                                          wire_dtype="bfloat16",
                                          data_axes=("data",)))


def test_unfused_ring_fails_at_construction():
    with pytest.raises(ValueError, match="fuse"):
        Communicator(_mesh1(), CommConfig(transport="ring", fuse=False,
                                          data_axes=("data",)))


def test_psum_reduce_scatter_rejected():
    comm = Communicator(_mesh1(), CommConfig(transport="psum",
                                             data_axes=("data",)))
    import jax.numpy as jnp

    with pytest.raises(ValueError, match="reduce-scatter"):
        comm.reduce_scatter([jnp.zeros((8,), jnp.float32)])


# ---------------------------------------------------------------------------
# channel striping
# ---------------------------------------------------------------------------


def test_stripe_partitions_every_bucket_exactly_once():
    sizes = [512, 128, 1024, 256, 256, 64, 2048]
    for n_channels in (1, 2, 3, 4, 7, 9):
        assignments = assign_channels(sizes, n_channels)
        assert len(assignments) == n_channels
        seen = [i for a in assignments for i in a.buckets]
        assert sorted(seen) == list(range(len(sizes)))   # round-trip
        for a in assignments:
            assert a.elems == sum(sizes[i] for i in a.buckets)
            assert list(a.buckets) == sorted(a.buckets)


def test_stripe_is_deterministic_and_balanced():
    sizes = [100] * 8
    a1 = assign_channels(sizes, 4)
    a2 = assign_channels(sizes, 4)
    assert a1 == a2
    assert all(len(a.buckets) == 2 and a.elems == 200 for a in a1)


def test_communicator_stripe_and_plan():
    comm = Communicator(_mesh1(), CommConfig(transport="ring_hier",
                                             data_axes=("data",), channels=2,
                                             bucket_bytes=4096))
    import jax

    tree = {f"p{i}": jax.ShapeDtypeStruct((600,), np.float32)
            for i in range(5)}
    plan = comm.plan(tree)
    assert plan.n_channels == 2
    assert plan.transport == "ring_hier"
    covered = sorted(i for a in plan.channels for i in a.buckets)
    assert covered == list(range(plan.n_buckets))
    pb = plan.predicted_collective_bytes()
    assert pb["grad_bytes"] == 5 * 600 * 4
    assert pb["bytes_per_device"] == 0.0          # world == 1: no wire bytes
    desc = plan.describe()
    assert desc["world"] == 1 and desc["n_buckets"] == plan.n_buckets
    # channels=0 -> every bucket is its own independent channel
    comm0 = Communicator(_mesh1(), CommConfig(transport="ring_hier",
                                              data_axes=("data",),
                                              bucket_bytes=4096))
    assert comm0.plan(tree).n_channels == comm0.plan(tree).n_buckets


# ---------------------------------------------------------------------------
# numerical equivalence vs lax.psum (1-D mesh, 4 fake devices)
# ---------------------------------------------------------------------------

EQUIV_SCRIPT = r"""
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P
from repro.comm import CommConfig, Communicator, list_transports

mesh = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,) * 1)
rng = np.random.RandomState(0)
tree = {f"g{i}": jnp.asarray(rng.randn(3000 + 256*i).astype(np.float32))
        for i in range(4)}
specs = {k: P() for k in tree}

def per_device(g):
    i = jax.lax.axis_index("data")
    return jax.tree.map(lambda t: t * (1.0 + i), g)

gv = jax.jit(jax.shard_map(per_device, mesh=mesh, in_specs=(specs,),
                           out_specs=specs, check_vma=False))(tree)
ref = jax.jit(jax.shard_map(
    lambda g: jax.tree.map(lambda x: jax.lax.pmean(x, "data"), g),
    mesh=mesh, in_specs=(specs,), out_specs=specs, check_vma=False))(gv)

cases = [(t, 0) for t in list_transports()] + [("ring_hier", 2), ("ring", 4)]
for transport, channels in cases:
    comm = Communicator(mesh, CommConfig(transport=transport, chunks=2,
                                         channels=channels,
                                         data_axes=("data",)))
    out, _ = comm.reduce(gv, specs)
    err = max(float(jnp.abs(out[k] - ref[k]).max()) for k in tree)
    assert err < 1e-4, (transport, channels, err)
    print(transport, channels, "ok", err)

# quantized wire rides any ring transport via wire_codec (the removed
# ring_compressed transport's replacement spelling)
comm_q = Communicator(mesh, CommConfig(transport="ring_hier", chunks=2,
                                       wire_codec="int8",
                                       data_axes=("data",)))
out, _ = comm_q.reduce(gv, specs)
err = max(float(jnp.abs(out[k] - ref[k]).max()) for k in tree)
assert err < 0.08, ("ring_hier+int8", err)

# legacy shim delegates to the same machinery (all six policies get full
# coverage in the slow distributed suite; one per transport family here)
import warnings
from repro.core.reducer import GradientReducer, ReduceConfig
with warnings.catch_warnings():
    warnings.simplefilter("ignore", DeprecationWarning)
    for policy in ["baidu_original", "fused_ring_hierarchical",
                   "native_psum_fused"]:
        kw = dict(bucket_bytes=1) if policy == "baidu_original" else {}
        red = GradientReducer(mesh, ReduceConfig(policy=policy,
                                                 data_axes=("data",),
                                                 chunks=2, **kw))
        out, _ = red.reduce(gv, specs)
        err = max(float(jnp.abs(out[k] - ref[k]).max()) for k in tree)
        assert err < 1e-4, (policy, err)
print("COMM_EQUIV_OK")
"""


def test_transports_match_psum_on_1d_mesh():
    assert "COMM_EQUIV_OK" in run_distributed(EQUIV_SCRIPT, n_devices=4)


# ---------------------------------------------------------------------------
# GradientBucketer: the oversized-leaf invariant (a leaf larger than
# bucket_bytes becomes a singleton bucket, never split) and its corollaries
# ---------------------------------------------------------------------------


def _plan_of(tree, bucket_bytes=1024, pad=128):
    from repro.core.bucketing import GradientBucketer

    b = GradientBucketer(bucket_bytes=bucket_bytes, pad_multiple=pad)
    return b, b.plan(tree)


def _bucket_of_leaf(plan):
    return {f.leaf: f.bucket for f in plan.fields}


def test_oversized_leaf_is_singleton_bucket():
    import jax.numpy as jnp

    # cap = 1024 B / 4 = 256 elements; the 1000-element leaf overflows it
    big = jnp.zeros((1000,), jnp.float32)
    small = jnp.zeros((10,), jnp.float32)
    for order in (["a_big", "b_s1", "c_s2"],      # oversized first
                  ["a_s1", "b_big", "c_s2"],      # oversized in the middle
                  ["a_s1", "b_s2", "c_big"]):     # oversized last
        tree = {k: (big if "big" in k else small) for k in order}
        _, plan = _plan_of(tree)
        by_leaf = _bucket_of_leaf(plan)
        leaves = sorted(tree)                     # dict flatten order
        big_leaf = next(i for i, k in enumerate(leaves) if "big" in k)
        big_bucket = by_leaf[big_leaf]
        # nothing shares the oversized leaf's bucket
        assert [l for l, bk in by_leaf.items() if bk == big_bucket] == \
            [big_leaf], order
        # and the leaf was not split: its field spans its full size, and
        # the bucket is exactly its padded size
        f = next(f for f in plan.fields if f.leaf == big_leaf)
        assert f.size == 1000 and f.offset == 0
        assert plan.bucket_sizes[big_bucket] == 1024  # 1000 padded to 128s


def test_adjacent_oversized_leaves_stay_separate():
    import jax.numpy as jnp

    tree = {"a": jnp.zeros((500,), jnp.float32),
            "b": jnp.zeros((700,), jnp.float32)}
    _, plan = _plan_of(tree)
    by_leaf = _bucket_of_leaf(plan)
    assert by_leaf[0] != by_leaf[1]
    assert plan.n_buckets == 2


def test_small_leaves_after_oversized_open_fresh_bucket():
    import jax.numpy as jnp

    tree = {"a": jnp.zeros((300,), jnp.float32),   # > 256-elem cap
            "b": jnp.zeros((10,), jnp.float32),
            "c": jnp.zeros((10,), jnp.float32)}
    _, plan = _plan_of(tree)
    by_leaf = _bucket_of_leaf(plan)
    assert by_leaf[0] == 0
    assert by_leaf[1] == by_leaf[2] == 1           # both fit bucket 1
    assert plan.n_buckets == 2


def test_oversized_roundtrip_and_padding_accounting():
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.RandomState(0)
    tree = {"a": jnp.asarray(rng.randn(333).astype(np.float32)),
            "b": jnp.asarray(rng.randn(7).astype(np.float32))}
    b, plan = _plan_of(tree)
    buckets, _ = b.bucketize(tree)
    assert [int(x.shape[0]) for x in buckets] == list(plan.bucket_sizes)
    back = b.debucketize(buckets, plan)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(back[k]),
                                      np.asarray(tree[k]))
    assert plan.used_elems == 340
    assert plan.total_elems == sum(plan.bucket_sizes)


# ---------------------------------------------------------------------------
# latency model: t_collective = alpha * messages + bytes / bw
# ---------------------------------------------------------------------------


def test_latency_model_alpha_beta_split():
    from repro.comm import ALPHA_S, LatencyModel

    m = LatencyModel()
    assert m.collective_seconds(0, 0) == 0.0
    # pure-latency regime: tiny payload, many messages
    assert m.collective_seconds(100, 8) == pytest.approx(
        100 * ALPHA_S + 8 / m.bandwidth)
    # alpha dominates small messages, beta dominates bulk
    small = m.collective_seconds(10, 1024)
    bulk = m.collective_seconds(10, 10 * 2**30)
    assert small == pytest.approx(10 * ALPHA_S, rel=2e-2)
    assert bulk == pytest.approx(10 * 2**30 / m.bandwidth, rel=2e-2)


def test_transport_message_counts():
    from repro.core.ring import RingConfig

    def transport_for(name, **ring_kw):
        _, cls = get_transport(name)
        return cls(("data",), RingConfig(**ring_kw))

    # psum: one ring over the joint world = 2*(p-1) hops
    assert transport_for("psum").predicted_messages_per_device([4]) == 6.0
    assert transport_for("psum").predicted_messages_per_device(
        [2, 4]) == 14.0
    assert transport_for("psum").predicted_messages_per_device([1]) == 0.0
    # explicit bidirectional 2-chunk ring: 4 parallel chains, same hop count
    ring = transport_for("ring", chunks=2, bidirectional=True)
    assert ring.predicted_messages_per_device([4]) == 6.0 * 4
    uni = transport_for("ring", chunks=1, bidirectional=False)
    assert uni.predicted_messages_per_device([4]) == 6.0
    # message count scales with buckets through CommPlan (axis size 1 mesh:
    # no wire, so just check the field and describe key are wired through)
    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType

    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,) * 1)
    comm = Communicator(mesh, CommConfig(transport="psum",
                                         data_axes=("data",)))
    plan = comm.plan({"w": jnp.zeros((512,), jnp.float32)})
    assert plan.messages_per_device == 0.0
    assert "messages_per_device" in plan.describe()
    assert plan.predicted_collective_seconds() >= 0.0


def test_halo_plan_message_count_is_unit_count():
    import jax
    from jax.sharding import AxisType

    from repro.core.halo import HaloSpec

    mesh = jax.make_mesh((1,), ("x",), axis_types=(AxisType.Auto,) * 1)
    comm = Communicator(mesh, CommConfig(data_axes=("x",), channels=2))
    specs = [HaloSpec("x", 0, 1)]
    plan = comm.halo_plan((6, 5), specs, schedule="concurrent")
    assert plan.messages_per_device == plan.n_units == 2
    assert plan.describe()["messages_per_device"] == 2
    assert plan.predicted_collective_seconds() == pytest.approx(
        2 * 1.5e-6 + plan.bytes_per_device / 50e9)


# ---------------------------------------------------------------------------
# all-to-all: capability gating, predicted pricing, schedule, equivalence
# ---------------------------------------------------------------------------


def _a2a_comm(transport="a2a", channels=0):
    import jax
    from jax.sharding import AxisType

    mesh = jax.make_mesh((1,), ("model",), axis_types=(AxisType.Auto,) * 1)
    return Communicator(mesh, CommConfig(transport=transport,
                                         data_axes=("model",),
                                         channels=channels))


def test_a2a_needs_single_axis_and_capability():
    import jax
    from jax.sharding import AxisType

    mesh2 = jax.make_mesh((1, 1), ("pod", "data"),
                          axis_types=(AxisType.Auto,) * 2)
    comm2 = Communicator(mesh2, CommConfig(transport="a2a",
                                           data_axes=("pod", "data")))
    import jax.numpy as jnp

    with pytest.raises(ValueError, match="exactly one comm axis"):
        comm2.all_to_all(jnp.zeros((4, 4)), split_axis=0, concat_axis=1)


def test_a2a_predicted_messages_and_bytes():
    _, cls = get_transport("a2a")
    t = cls(("model",), None)
    # ring-style pricing: p-1 pairwise hops, (p-1)/p of the buffer crosses
    assert t.predicted_a2a_messages_per_device(4) == 3.0
    assert t.predicted_a2a_messages_per_device(1) == 0.0
    assert t.predicted_a2a_bytes_per_device(1024, 4) == 1024 * 4 * 3 / 4
    # psum fallback prices the honest replicated cost: 2(p-1) full copies
    _, pcls = get_transport("psum")
    from repro.core.ring import RingConfig

    p = pcls(("model",), RingConfig())
    assert p.predicted_a2a_messages_per_device(4) == 6.0
    assert p.predicted_a2a_bytes_per_device(1024, 4) == 2 * 3 * 1024 * 4
    # the acceptance bound: dispatch bytes <= 1/R of the replicated cost
    for r in (2, 4, 8):
        assert (t.predicted_a2a_bytes_per_device(1 << 20, r)
                <= p.predicted_a2a_bytes_per_device(1 << 20, r) / r)


def test_a2a_plan_and_moe_schedule():
    comm = _a2a_comm(channels=2)
    shape = (4, 8, 16, 64)           # last dim divisible by channels=2
    plan = comm.a2a_plan(shape)
    assert plan.n_units == 4                        # dispatch+combine x rails
    assert sorted(k.split("#")[0] for k in plan.unit_keys) == \
        ["combine", "combine", "dispatch", "dispatch"]
    assert plan.bytes_per_device == 0.0             # axis size 1: no wire
    assert plan.dispatch_bytes_per_device == 0.0
    assert plan.describe()["transport"] == "a2a"
    assert plan.predicted_collective_seconds() >= 0.0
    sched = comm.moe_schedule(shape)
    sched.validate()
    assert sched.policy == "moe" and sched.channels == 2
    assert sched.n_buckets == 4
    # rails fall back to 1 when the feature dim doesn't divide
    assert comm.a2a_rails((4, 8, 16, 63)) == 1
    assert comm.a2a_rails(shape) == 2


def test_a2a_axis_size_one_is_identity():
    import jax.numpy as jnp

    for transport in ("a2a", "ring", "ring_hier", "psum"):
        comm = _a2a_comm(transport=transport)
        x = jnp.arange(8.0).reshape(2, 4)
        out = comm.all_to_all(x, split_axis=0, concat_axis=1)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


A2A_SCRIPT = r"""
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P
from repro.comm import CommConfig, Communicator

mesh = jax.make_mesh((4,), ("model",), axis_types=(AxisType.Auto,) * 1)
rng = np.random.RandomState(0)
x = jnp.asarray(rng.randn(4, 8, 3, 12).astype(np.float32))

def native(v):
    return jax.lax.all_to_all(v, "model", 1, 0, tiled=True)

ref = jax.jit(jax.shard_map(native, mesh=mesh, in_specs=P(),
                            out_specs=P("model"), check_vma=False))(x)

for transport in ("a2a", "ring", "ring_hier", "psum"):
    for channels in (0, 2, 3):
        comm = Communicator(mesh, CommConfig(transport=transport,
                                             data_axes=("model",),
                                             channels=channels))

        def fwd(v):
            return comm.all_to_all(v, split_axis=1, concat_axis=0)

        out = jax.jit(jax.shard_map(fwd, mesh=mesh, in_specs=P(),
                                    out_specs=P("model"),
                                    check_vma=False))(x)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
        print(transport, channels, "fwd ok")

# gradient check once per transport (native reference transpose)
def loss_ref(v, w_local):
    return jnp.sum(native(v) * w_local)

w = jnp.asarray(rng.randn(64, 2, 3, 12).astype(np.float32))
gref = jax.jit(jax.shard_map(
    jax.grad(loss_ref), mesh=mesh, in_specs=(P(), P("model")),
    out_specs=P(), check_vma=False))(x, w)
for transport in ("a2a", "ring", "psum"):
    comm = Communicator(mesh, CommConfig(transport=transport,
                                         data_axes=("model",)))

    def loss_t(v, w_local):
        return jnp.sum(comm.all_to_all(v, split_axis=1, concat_axis=0)
                       * w_local)

    g = jax.jit(jax.shard_map(
        jax.grad(loss_t), mesh=mesh, in_specs=(P(), P("model")),
        out_specs=P(), check_vma=False))(x, w)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gref),
                               rtol=1e-6, atol=1e-6)
    print(transport, "grad ok")

# ragged: counts travel with the payload
comm = Communicator(mesh, CommConfig(transport="a2a",
                                     data_axes=("model",)))

def ragged(v):
    i = jax.lax.axis_index("model")
    counts = jnp.arange(4, dtype=jnp.int32) + 10 * i   # count j for dest j
    recv, rc = comm.all_to_all_ragged(v, counts, split_axis=1,
                                      concat_axis=0)
    return recv, rc

_, rc = jax.jit(jax.shard_map(ragged, mesh=mesh, in_specs=P(),
                              out_specs=(P("model"), P("model")),
                              check_vma=False))(x)
rc = np.asarray(rc).reshape(4, 4)
for i in range(4):
    for j in range(4):
        assert rc[i, j] == i + 10 * j, (i, j, rc[i, j])   # from src j: j's count for dest i
print("A2A_EQUIV_OK")
"""


def test_all_to_all_matches_native_on_1d_mesh():
    assert "A2A_EQUIV_OK" in run_distributed(A2A_SCRIPT, n_devices=4)


def test_roofline_alpha_term():
    from repro.launch.roofline import ICI_BW, Roofline

    base = Roofline(flops_per_device=1e12, hbm_bytes_per_device=1e9,
                    wire_bytes_per_device=1e6)
    with_alpha = Roofline(flops_per_device=1e12, hbm_bytes_per_device=1e9,
                          wire_bytes_per_device=1e6,
                          messages_per_device=1000)
    # default (no count) keeps the pure-bandwidth behaviour
    assert base.t_collective == pytest.approx(1e6 / ICI_BW)
    assert with_alpha.t_collective == pytest.approx(
        1e6 / ICI_BW + 1000 * with_alpha.alpha_s)
    assert with_alpha.t_exposed_collective <= with_alpha.t_collective
    assert with_alpha.as_dict(8)["messages_per_device"] == 1000

"""repro.mem — the page-aligned CommArena subsystem.

Layout invariants (page-quantized offsets, non-overlap, padding
accounting), the oversized-leaf warning, Pallas pack kernels vs the jnp
oracle (bitwise), span-fused schedules, the fused-collective claim in
lowered HLO, a 2-proc cross-transport regression, checkpoint round-trips
across ``use_arena`` toggles, and (slow) full train-step equivalence of the
arena path for all three DP modes."""

import numpy as np
import pytest

from conftest import run_distributed

from repro.comm import CommConfig, Communicator, build_schedule
from repro.mem import (ArenaLayout, CommArena, PAGE_BYTES, fuse_schedule,
                       plan_arena)


def _mesh1():
    import jax
    from jax.sharding import AxisType

    return jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,) * 1)


# ---------------------------------------------------------------------------
# layout invariants
# ---------------------------------------------------------------------------

SIZES = (512, 128, 1024, 256, 256, 64)


@pytest.mark.parametrize("page_bytes", [512, 4096, 2 * 2**20])
@pytest.mark.parametrize("channel_of", [None, [0, 1, 0, 1, 0, 1],
                                        [2, 2, 0, 1, 0, 2]])
def test_layout_invariants(page_bytes, channel_of):
    lay = plan_arena(SIZES, page_bytes=page_bytes, channel_of=channel_of,
                     pad_multiple=8)
    lay.validate()                       # offsets quantized, non-overlapping
    quantum = lay.quantum
    assert quantum % (page_bytes // 4) == 0
    end = 0
    for s in sorted(lay.segments, key=lambda s: s.offset):
        assert s.offset % quantum == 0 and s.padded % quantum == 0
        assert s.offset >= end           # non-overlapping, ordered
        end = s.offset + s.padded
    assert lay.total_elems == end
    # every bucket appears exactly once, in exactly one span
    assert sorted(s.bucket for s in lay.segments) == list(range(len(SIZES)))
    span_members = [b for sp in lay.spans for b in sp.buckets]
    assert sorted(span_members) == list(range(len(SIZES)))
    # padding fraction matches the prediction identity
    assert lay.used_elems == sum(SIZES)
    assert lay.padding_elems == lay.total_elems - sum(SIZES)
    assert lay.padding_fraction == pytest.approx(
        1.0 - sum(SIZES) / lay.total_elems)
    # whole pages, exactly
    assert lay.total_bytes == lay.n_pages * page_bytes or \
        lay.total_bytes % page_bytes == 0
    d = lay.describe()
    assert d["n_pages"] == lay.n_pages
    assert d["padding_fraction"] == lay.padding_fraction
    assert len(d["segments"]) == len(SIZES)


def test_layout_channel_grouping_is_contiguous():
    lay = plan_arena(SIZES, page_bytes=512, channel_of=[1, 0, 1, 0, 1, 0])
    assert lay.n_spans == 2
    for sp in lay.spans:
        run = sp.offset
        for b in sp.buckets:
            seg = lay.segment_of(b)
            assert seg.offset == run and seg.channel == sp.channel
            run += seg.padded
        assert run - sp.offset == sp.size


def test_plan_arena_rejects_bad_args():
    with pytest.raises(ValueError, match="page_bytes"):
        plan_arena(SIZES, page_bytes=0)
    with pytest.raises(ValueError, match="page_bytes"):
        plan_arena(SIZES, page_bytes=129)       # not an itemsize multiple
    with pytest.raises(ValueError, match="channel_of"):
        plan_arena(SIZES, channel_of=[0, 1])
    with pytest.raises(ValueError, match="pad_multiple"):
        plan_arena(SIZES, pad_multiple=0)


def test_default_page_is_the_papers_huge_page():
    assert PAGE_BYTES == 2 * 2**20
    lay = plan_arena([100])
    assert lay.total_bytes % PAGE_BYTES == 0
    assert CommConfig().page_bytes == PAGE_BYTES


# ---------------------------------------------------------------------------
# oversized-leaf buckets: dedicated page-aligned segments + one warning
# ---------------------------------------------------------------------------


def test_oversized_bucket_warns_once_and_gets_dedicated_segment():
    import warnings as w

    import jax.numpy as jnp

    from repro.core.bucketing import GradientBucketer
    from repro.mem import arena_from_bucket_plan
    from repro.mem import layout as mem_layout

    bucketer = GradientBucketer(bucket_bytes=1024, pad_multiple=128)
    tree = {"big": jnp.zeros((1000,), jnp.float32),   # > 256-elem target
            "s1": jnp.zeros((10,), jnp.float32),
            "s2": jnp.zeros((10,), jnp.float32)}
    plan = bucketer.plan(tree)
    mem_layout._warned_oversized = False
    with w.catch_warnings(record=True) as rec:
        w.simplefilter("always")
        lay = arena_from_bucket_plan(plan, page_bytes=512,
                                     bucket_bytes=1024)
    msgs = [r for r in rec if issubclass(r.category, RuntimeWarning)]
    assert len(msgs) == 1 and "oversized" in str(msgs[0].message)
    # the warning fires once per process, not once per plan
    with w.catch_warnings(record=True) as rec2:
        w.simplefilter("always")
        arena_from_bucket_plan(plan, page_bytes=512, bucket_bytes=1024)
    assert not [r for r in rec2 if issubclass(r.category, RuntimeWarning)]
    # the oversized bucket is a dedicated page-aligned segment like any other
    big_bucket = next(f.bucket for f in plan.fields if f.size == 1000)
    seg = lay.segment_of(big_bucket)
    assert seg.offset % lay.quantum == 0
    assert seg.size == plan.bucket_sizes[big_bucket]
    lay.validate()
    # no warning when every bucket meets the target
    mem_layout._warned_oversized = False
    small = bucketer.plan({"a": jnp.zeros((10,), jnp.float32)})
    with w.catch_warnings(record=True) as rec3:
        w.simplefilter("always")
        arena_from_bucket_plan(small, page_bytes=512, bucket_bytes=1024)
    assert not [r for r in rec3 if issubclass(r.category, RuntimeWarning)]
    # pure-prediction paths (Communicator.plan -> every dry-run cell) stay
    # silent even with oversized leaves; only arena construction warns
    mem_layout._warned_oversized = False
    comm = Communicator(_mesh1(), CommConfig(transport="ring_hier",
                                             data_axes=("data",),
                                             bucket_bytes=1024))
    with w.catch_warnings(record=True) as rec4:
        w.simplefilter("always")
        comm.plan(tree)
    assert not [r for r in rec4 if issubclass(r.category, RuntimeWarning)]
    with w.catch_warnings(record=True) as rec5:
        w.simplefilter("always")
        comm.arena(tree)
    assert [r for r in rec5 if issubclass(r.category, RuntimeWarning)]


# ---------------------------------------------------------------------------
# CommArena pack/unpack: jnp vs Pallas bitwise, dirty-buffer pack_into
# ---------------------------------------------------------------------------


def _random_buffers(rng, sizes):
    import jax.numpy as jnp

    return [jnp.asarray(rng.randn(n).astype(np.float32)) for n in sizes]


def test_pack_unpack_pallas_matches_ref_bitwise(rng):
    import jax.numpy as jnp

    lay = plan_arena(SIZES, page_bytes=4096, channel_of=[0, 1, 0, 1, 0, 1])
    bufs = _random_buffers(rng, SIZES)
    a_ref = CommArena(lay, impl="jnp")
    a_pal = CommArena(lay, impl="pallas")
    packed_ref = np.asarray(a_ref.pack(bufs))
    packed_pal = np.asarray(a_pal.pack(bufs))
    assert np.array_equal(packed_ref, packed_pal)          # bitwise
    for b, u_r, u_p in zip(bufs, a_ref.unpack(a_ref.pack(bufs)),
                           a_pal.unpack(a_pal.pack(bufs))):
        assert np.array_equal(np.asarray(b), np.asarray(u_r))
        assert np.array_equal(np.asarray(u_r), np.asarray(u_p))
    # pack_into a dirty persistent buffer: segments overwritten, padding
    # keeps the old bytes (never read back), round-trip exact
    dirty = jnp.full((lay.total_elems,), 7.25, jnp.float32)
    for arena in (a_ref, a_pal):
        out = arena.pack_into(dirty, bufs)
        for b, u in zip(bufs, arena.unpack(out)):
            assert np.array_equal(np.asarray(b), np.asarray(u))
        pad_mask = np.ones(lay.total_elems, bool)
        for s in lay.segments:
            pad_mask[s.offset:s.offset + s.size] = False
        assert np.all(np.asarray(out)[pad_mask] == 7.25)


def test_unpack_spans_matches_unpack(rng):
    lay = plan_arena(SIZES, page_bytes=512, channel_of=[0, 1, 0, 1, 0, 1])
    bufs = _random_buffers(rng, SIZES)
    arena = CommArena(lay)
    packed = arena.pack(bufs)
    spans = [packed[sp.offset:sp.offset + sp.size] for sp in lay.spans]
    for a, b in zip(arena.unpack(packed), arena.unpack_spans(spans)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_arena_validation_errors(rng):
    import jax.numpy as jnp

    lay = plan_arena(SIZES, page_bytes=512)
    arena = CommArena(lay)
    with pytest.raises(ValueError, match="impl"):
        CommArena(lay, impl="cuda")
    with pytest.raises(ValueError, match="segments"):
        arena.pack(_random_buffers(rng, SIZES[:-1]))
    with pytest.raises(ValueError, match="arena shape"):
        arena.pack_into(jnp.zeros((3,), jnp.float32),
                        _random_buffers(rng, SIZES))
    with pytest.raises(ValueError, match="elems"):
        arena.pack([b[:-1] if i == 0 else b for i, b in
                    enumerate(_random_buffers(rng, SIZES))])


def test_pack_kernel_fallback_is_exact(rng):
    """Offsets/sizes off the (8·128) tiling route to the jnp oracle —
    correctness is never conditional on the fast path."""
    import jax.numpy as jnp

    from repro.kernels.pack import ops

    arena = jnp.zeros((1024,), jnp.float32)
    src = jnp.asarray(rng.randn(130).astype(np.float32))   # not lane-aligned
    out = ops.write_flat(arena, src, 3)                    # odd offset
    assert np.array_equal(np.asarray(out[3:133]), np.asarray(src))
    back = ops.read_flat(out, 3, 130)
    assert np.array_equal(np.asarray(back), np.asarray(src))


# ---------------------------------------------------------------------------
# fused span schedules
# ---------------------------------------------------------------------------


def test_fuse_schedule_invariants():
    # quantum == lane multiple and lane-aligned sizes -> zero padding, so
    # the overlap comparison is apples-to-apples (fused readiness waits for
    # the span's last member)
    sizes = (512, 128, 1024, 256, 256, 128)
    chan = [0, 1, 0, 1, 0, 1]
    lay = plan_arena(sizes, page_bytes=512, channel_of=chan)
    assert lay.padding_elems == 0
    for policy in ("accumulate_then_reduce", "stream", "scheduled"):
        for m in (1, 3):
            sched = build_schedule(policy, sizes, microbatches=m, channels=2)
            fused = fuse_schedule(sched, lay)
            fused.validate()
            assert fused.n_buckets == lay.n_spans
            assert fused.policy == policy and fused.microbatches == m
            phases = m if policy != "accumulate_then_reduce" else 1
            assert fused.n_collectives == lay.n_spans * phases
            assert fused.overlap_fraction <= sched.overlap_fraction + 1e-12
    with pytest.raises(ValueError, match="segments"):
        fuse_schedule(build_schedule("stream", sizes[:-1]), lay)


def test_arena_from_halo_plan_groups_by_rail():
    from repro.core.halo import HaloSpec
    from repro.mem import arena_from_halo_plan

    comm = Communicator(_mesh1(), CommConfig(transport="psum",
                                             data_axes=("data",),
                                             channels=2))
    hplan = comm.halo_plan((6, 5), [HaloSpec("data", 0, 1)],
                           schedule="overlap")
    lay = arena_from_halo_plan(hplan, page_bytes=512, pad_multiple=8)
    lay.validate()
    assert lay.n_segments == hplan.n_units
    # bytes -> elements, per unit
    for seg in lay.segments:
        assert seg.size == -(-hplan.unit_bytes[seg.bucket] // 4)
    # one contiguous span per halo rail
    assert lay.n_spans == len(hplan.channels)
    for sp, hc in zip(lay.spans, sorted(hplan.channels,
                                        key=lambda c: c.channel)):
        assert sorted(sp.buckets) == sorted(hc.units)


def test_communicator_arena_plan_and_schedule():
    import jax

    comm = Communicator(_mesh1(), CommConfig(
        transport="ring_hier", data_axes=("data",), channels=2,
        bucket_bytes=4096, page_bytes=4096))
    tree = {f"p{i}": jax.ShapeDtypeStruct((600,), np.float32)
            for i in range(5)}
    plan = comm.plan(tree)
    lay = plan.arena_layout
    assert isinstance(lay, ArenaLayout)
    assert lay.n_spans == 2                        # one span per rail
    assert lay.n_segments == plan.n_buckets
    # fused message count: one send-chain per span instead of per bucket
    assert plan.arena_messages_per_device <= plan.messages_per_device
    pb = plan.predicted_collective_bytes()
    assert pb["arena_pages"] == lay.n_pages
    assert pb["arena_padding_fraction"] == lay.padding_fraction
    assert plan.describe()["arena"]["total_bytes"] == lay.total_bytes
    fused = comm.arena_schedule(tree, "scheduled", 2)
    assert fused.n_buckets == lay.n_spans
    # impl knob follows local_op
    assert comm.arena(tree).impl == "jnp"
    comm_p = Communicator(_mesh1(), CommConfig(
        transport="ring_hier", data_axes=("data",), local_op="pallas"))
    assert comm_p.arena(tree).impl == "pallas"


# ---------------------------------------------------------------------------
# HLO: fused spans lower to fewer collectives than per-bucket issue, and
# the donated per-device arena buffer appears at its exact predicted size
# ---------------------------------------------------------------------------

HLO_FUSE_SCRIPT = r"""
import jax, jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P
from repro.comm import CommConfig, Communicator

mesh = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,) * 1)
comm = Communicator(mesh, CommConfig(transport="psum", data_axes=("data",),
                                     channels=2, bucket_bytes=4096,
                                     page_bytes=4096))
tree = {f"g{i}": jax.ShapeDtypeStruct((600,), jnp.float32) for i in range(6)}
arena = comm.arena(tree)
lay = arena.layout
sched_b = comm.schedule(tree, "scheduled", 1)
sched_a = comm.arena_schedule(tree, "scheduled", 1)
batch = {"x": jax.ShapeDtypeStruct((1,), jnp.float32)}

def gfn(p, mb):
    return jnp.zeros((), jnp.float32), p

def bucket_fn(grads, b):
    _, tree = comm.reduce_scheduled(gfn, grads, b, sched_b, op="all_reduce")
    return tree

def arena_fn(buf, grads, b):
    _, (tree, out) = comm.reduce_scheduled(gfn, grads, b, sched_a,
                                           op="all_reduce", arena=arena,
                                           arena_buf=buf)
    return out, tree

spec = {k: P() for k in tree}
fb = jax.jit(jax.shard_map(bucket_fn, mesh=mesh, in_specs=(spec, P()),
                           out_specs=spec, check_vma=False))
fa = jax.jit(jax.shard_map(arena_fn, mesh=mesh,
                           in_specs=(P(("data",)), spec, P()),
                           out_specs=(P(("data",)), spec),
                           check_vma=False), donate_argnums=(0,))
arena_abs = jax.ShapeDtypeStruct((4 * lay.total_elems,), jnp.float32)
ca = fa.lower(arena_abs, tree, batch).compile()
cb = fb.lower(tree, batch).compile()

from repro.launch.roofline import collective_wire_bytes
na = collective_wire_bytes(ca.as_text()).op_counts.get("all-reduce", 0)
nb = collective_wire_bytes(cb.as_text()).op_counts.get("all-reduce", 0)
assert nb == sched_b.n_buckets == 6, nb
assert na == lay.n_spans == 2, na
assert na < nb, (na, nb)
# the donated per-device arena appears at its exact page-quantized size
assert f"f32[{lay.total_elems}]" in ca.as_text(), lay.total_elems
# donation aliased the (per-device) arena buffer: memory_analysis is on
# the partitioned module
ma = ca.memory_analysis()
assert ma.alias_size_in_bytes >= lay.total_elems * 4, ma.alias_size_in_bytes
print("MEM_HLO_FUSE_OK")
"""


def test_fused_spans_lower_to_fewer_collectives():
    assert "MEM_HLO_FUSE_OK" in run_distributed(HLO_FUSE_SCRIPT, n_devices=4)


# ---------------------------------------------------------------------------
# cross-transport regression: arena reduction agrees between the explicit
# ring schedule and the vendor collective on 2 procs (pairwise sums commute
# -> bitwise with backend fusion disabled; see repro/stencil/op.py)
# ---------------------------------------------------------------------------

CROSS_TRANSPORT_SCRIPT = r"""
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P
from repro.comm import CommConfig, Communicator

mesh = jax.make_mesh((2,), ("data",), axis_types=(AxisType.Auto,) * 1)
rng = np.random.RandomState(3)
tree = {f"g{i}": jnp.asarray(rng.randn(500 + 128 * i).astype(np.float32))
        for i in range(4)}
batch = jnp.zeros((2,), jnp.float32)

def gfn(p, mb):
    i = jax.lax.axis_index("data").astype(jnp.float32)
    return jnp.zeros((), jnp.float32), jax.tree.map(
        lambda t: t * (1.0 + i), p)

outs = {}
for transport in ("ring_hier", "psum"):
    comm = Communicator(mesh, CommConfig(transport=transport,
                                         data_axes=("data",), channels=2,
                                         bucket_bytes=2048,
                                         page_bytes=1024, chunks=1))
    arena = comm.arena(tree)
    sched = comm.arena_schedule(tree, "scheduled", 1)
    def run(grads, b, buf):
        _, (t, out) = comm.reduce_scheduled(gfn, grads, b, sched,
                                            op="all_reduce", arena=arena,
                                            arena_buf=buf)
        return t
    spec = {k: P() for k in tree}
    fn = jax.jit(jax.shard_map(run, mesh=mesh,
                               in_specs=(spec, P("data"), P(("data",))),
                               out_specs=spec, check_vma=False))
    buf = jnp.zeros((2 * arena.layout.total_elems,), jnp.float32)
    outs[transport] = fn(tree, batch, buf)

for k in tree:
    a = np.asarray(outs["ring_hier"][k])
    b = np.asarray(outs["psum"][k])
    assert np.array_equal(a, b), (k, np.abs(a - b).max())
print("MEM_CROSS_TRANSPORT_OK")
"""


def test_arena_cross_transport_bitwise_2proc():
    out = run_distributed(CROSS_TRANSPORT_SCRIPT, n_devices=2,
                          extra_flags="--xla_disable_hlo_passes=fusion")
    assert "MEM_CROSS_TRANSPORT_OK" in out


# ---------------------------------------------------------------------------
# checkpoint round-trip: use_arena=True state restores into a non-arena
# step and vice versa (path-matched restore drops/keeps the scratch buffer)
# ---------------------------------------------------------------------------


def test_checkpoint_roundtrip_across_use_arena(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType, PartitionSpec as P
    from repro.checkpoint import restore, save
    from repro.configs import reduced_config
    from repro.models import build_model
    from repro.runtime.train_step import (TrainStepConfig, build_train_step,
                                          init_train_state)

    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    model = build_model(reduced_config("llama3.2-1b"))
    rng = np.random.RandomState(0)
    batch = {"tokens": jnp.asarray(rng.randint(0, 500, (4, 32)), jnp.int32),
             "labels": jnp.asarray(rng.randint(0, 500, (4, 32)), jnp.int32)}
    bspecs = {"tokens": P("data", None), "labels": P("data", None)}

    def cfg(use_arena):
        return TrainStepConfig(
            dp_mode="replicated",
            comm=CommConfig(transport="ring_hier", bucket_bytes=1 << 20,
                            page_bytes=1 << 12),
            use_arena=use_arena)

    def train(tcfg, state, n=2):
        with mesh:
            step = build_train_step(model, mesh, tcfg, bspecs)
            for _ in range(n):
                state, metrics = step(state, batch)
        return state, float(metrics["loss"])

    for src_arena, dst_arena in ((True, False), (False, True)):
        ckpt_dir = str(tmp_path / f"ck_{src_arena}")
        with mesh:
            state, _ = init_train_state(model, mesh, cfg(src_arena),
                                        key=jax.random.key(1))
        state, _ = train(cfg(src_arena), state)
        save(state, 2, ckpt_dir)
        # strict restore refuses the structure change...
        with mesh:
            like, _ = init_train_state(model, mesh, cfg(dst_arena),
                                       key=jax.random.key(2))
        with pytest.raises(ValueError, match="strict=False"):
            restore(like, 2, ckpt_dir)
        # ...path-matched restore carries the params across
        restored = restore(like, 2, ckpt_dir, strict=False)
        ref, ref_loss = train(cfg(src_arena), state, 1)
        got, got_loss = train(cfg(dst_arena), restored, 1)
        assert abs(ref_loss - got_loss) < 1e-5, (src_arena, ref_loss,
                                                 got_loss)


# ---------------------------------------------------------------------------
# full train-step equivalence: arena vs bucket path for all three DP modes
# on a 1xN data mesh (slow distributed subprocess)
# ---------------------------------------------------------------------------

DP_EQUIV_SCRIPT = r"""
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P
from repro.comm import CommConfig
from repro.configs import reduced_config
from repro.models import build_model
from repro.runtime.train_step import (TrainStepConfig, build_train_step,
                                      init_train_state)

mesh = jax.make_mesh((4, 1), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
model = build_model(reduced_config("llama3.2-1b"))
rng = np.random.RandomState(0)
batch = {"tokens": jnp.asarray(rng.randint(0, 500, (8, 32)), jnp.int32),
         "labels": jnp.asarray(rng.randint(0, 500, (8, 32)), jnp.int32)}
bspecs = {"tokens": P("data", None), "labels": P("data", None)}

def run(mode, use_arena):
    tcfg = TrainStepConfig(
        dp_mode=mode,
        comm=CommConfig(transport="ring_hier", chunks=2, channels=2,
                        bucket_bytes=1 << 20, page_bytes=1 << 12),
        microbatches=2, schedule="scheduled", use_arena=use_arena)
    with mesh:
        state, _ = init_train_state(model, mesh, tcfg, key=jax.random.key(7))
        step = build_train_step(model, mesh, tcfg, bspecs)
        for _ in range(2):
            state, metrics = step(state, batch)
    return state, metrics

def by_path(tree):
    return {jax.tree_util.keystr(p): l for p, l in
            jax.tree_util.tree_flatten_with_path(tree)[0]}

for mode in ("replicated", "zero1", "fsdp"):
    ref_state, ref_metrics = run(mode, False)
    st, mt = run(mode, True)
    assert abs(float(mt["loss"] - ref_metrics["loss"])) < 1e-5, mode
    assert abs(float(mt["grad_norm"] - ref_metrics["grad_norm"])) < 1e-4, \
        (mode, float(mt["grad_norm"]), float(ref_metrics["grad_norm"]))
    a, b = by_path(st), by_path(ref_state)
    for k in b:
        if "arena" in k:
            continue
        if mode == "zero1" and "'opt'" in k:
            continue   # optimizer shards re-laid out per fused span
        err = float(jnp.max(jnp.abs(a[k].astype(jnp.float32)
                                    - b[k].astype(jnp.float32))))
        assert err < 5e-5, (mode, k, err)
    print(mode, "arena equiv ok")
print("MEM_DP_EQUIV_OK")
"""


@pytest.mark.slow
def test_dp_mode_arena_equivalence():
    assert "MEM_DP_EQUIV_OK" in run_distributed(DP_EQUIV_SCRIPT, n_devices=4)

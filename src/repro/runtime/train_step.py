"""Train-step builders: the paper's communication engine fused into a
fully-manual SPMD step.

The step runs inside ``shard_map`` with **every** mesh axis manual: tensor
parallelism is explicit (``ParallelCtx.psum`` in the models), and the
data-parallel gradient reduction is the :class:`repro.comm.Communicator`'s
transport — XLA never inserts an opaque grad all-reduce, so §Perf
before/after measures the paper's technique and nothing else.  All three DP
modes draw their collectives from the same communicator: all-reduce
(replicated), reduce-scatter/all-gather of flat bucket shards (ZeRO-1), and
per-layer weight gather whose autodiff transpose is the reduce-scatter
(FSDP/ZeRO-3).

DP modes (rungs of the paper's ladder):

* ``replicated`` — params + optimizer state replicated over data; grads
  all-reduced (mean) by the communicator's transport.  The 2017 paper's
  setting.
* ``zero1``      — grads *reduce-scattered* into flat bucket shards; AdamW
  updates the shard; the param **delta** is ring-all-gathered and applied.
  Same comm volume as all-reduce (RS+AG), optimizer memory / dp_world.
* ``fsdp``       — ZeRO-3: per-layer-group params stored as flat bucket
  shards; each rematerialised layer ring-all-gathers its bf16 weights on
  entry, and the *autodiff transpose of that gather is exactly the ring
  reduce-scatter*, so gradients arrive pre-sharded for free.  Built entirely
  from the paper's collectives.

When each bucket's reduction is *issued* is no longer implicit: every mode
executes a :class:`repro.comm.schedule.CommSchedule`
(:func:`build_step_schedule`) via ``Communicator.reduce_scheduled``, so
streamed per-bucket reduction overlaps with remaining backward compute and
the dry-run/roofline layers can predict the exposed communication.

``use_arena`` switches all three modes onto the :mod:`repro.mem`
communication arena: gradients pack into one page-aligned, allocate-once
buffer carried in the train state and **donated** through the jitted step
(XLA reuses the allocation in place, the paper's persistent huge-page
registration).  ``replicated`` all-reduces fused contiguous spans (fewer,
larger, aligned messages); ``zero1`` reduce-scatters span shards; ``fsdp``
uses the arena as its microbatch accumulation buffer (its reduction rides
the gather transpose, so only buffer residency changes).

``wire_codec='int8'`` makes the wire quantized: with ``use_arena`` the
arena leaf becomes the int8 payload + fp32-scale buffer written by the
fused pack+quantize kernels (:mod:`repro.kernels.pack_quant`) and the
train state grows an ``"ef"`` leaf — the per-element error-feedback
residual, compensated into every encode so the quantization error
telescopes instead of accumulating.  Without the arena it falls back to
the legacy per-hop ring codec (the ring transports re-encode every hop).

MoE expert parallelism rides its own communicator: ``moe_transport`` /
``moe_channels`` configure the single-axis all-to-all the models reach via
``ParallelCtx.all_to_all`` (dispatch/combine of the capacity buffer), and
the routing layer's capacity-overflow drops surface as the
``moe_drop_fraction`` metric next to loss/grad_norm.
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import numpy as np

from repro.comm import CommConfig, Communicator
from repro.comm.schedule import CommSchedule, SCHEDULE_POLICIES, build_schedule
from repro.core.bucketing import BucketPlan
from repro.core.reducer import ReduceConfig
from repro.mem.arena import CommArena, QuantCommArena
from repro.mem.layout import (ArenaLayout, QuantArenaLayout, plan_arena,
                              plan_quant_arena)
from repro.models.model_api import Model
from repro.models.parallel import ParallelCtx
from repro.optim import (OptimConfig, adamw_flat_update, adamw_tree_update,
                         init_opt_state, make_schedule)
from repro.optim.adamw import clip_factor, global_grad_norm
from repro.sharding import rules as shard_rules
from repro.sharding.rules import MODEL_AXIS

DP_MODES = ("replicated", "zero1", "fsdp")


@dataclass(frozen=True)
class TrainStepConfig:
    dp_mode: str = "replicated"
    comm: CommConfig | None = None     # preferred: the Communicator config
    reduce: ReduceConfig = field(default_factory=ReduceConfig)  # legacy
    optim: OptimConfig = field(default_factory=OptimConfig)
    microbatches: int = 1              # grad-accumulation slices
    schedule: str = "accumulate_then_reduce"  # SCHEDULE_POLICIES member
    use_arena: bool = False            # repro.mem CommArena (page-aligned,
                                       # donated, fused-span collectives)
    wire_codec: str | None = None      # None | "int8": quantized wire; with
                                       # use_arena the arena is the int8
                                       # payload + scale buffer and the train
                                       # state carries the error-feedback
                                       # accumulator ("ef" leaf)
    causal_skip: bool = False
    gather_dtype: str = "bfloat16"     # fsdp weight-gather wire dtype
    fsdp_bucket_bytes: int = 512 * 2**20
    fsdp_gather: str = "native"        # "native" (one all-gather op) | "ring"
                                       # (our unrolled schedule; hillclimb knob)
    moe_transport: str = "a2a"         # EP dispatch/combine transport over the
                                       # model axis: "a2a" (native HLO
                                       # all-to-all) | "ring" | "ring_hier"
                                       # (ppermute hops) | "psum" (honest
                                       # replicated fallback)
    moe_channels: int = 0              # stripe the EP payload's feature dim
                                       # into N independent rails (0/1 = one)

    def comm_config(self, data_axes: tuple[str, ...]) -> CommConfig:
        """The communicator config for this step: ``comm`` when given,
        otherwise the legacy ``reduce`` policy mapped onto a transport."""
        ccfg = self.comm if self.comm is not None else self.reduce.comm_config()
        if self.wire_codec is not None:
            ccfg = replace(ccfg, wire_codec=self.wire_codec)
        if (ccfg.wire_codec is not None and self.dp_mode == "fsdp"
                and self.fsdp_gather == "ring"):
            # the codec encode (round/clip) has zero gradient, so the
            # unrolled ring gather's autodiff transpose — which IS the
            # fsdp reduction — would silently drop it
            raise ValueError(
                "wire_codec is incompatible with fsdp_gather='ring' "
                "(the reduction rides the gather transpose and the "
                "codec has no useful gradient); use fsdp_gather="
                "'native'")
        return replace(ccfg, data_axes=data_axes)

    @property
    def schedule_policy(self) -> str:
        """The (validated) issue-schedule family the step executes."""
        if self.schedule not in SCHEDULE_POLICIES:
            raise ValueError(f"unknown schedule policy {self.schedule!r}; "
                             f"one of {SCHEDULE_POLICIES}")
        return self.schedule


# ---------------------------------------------------------------------------
# mesh helpers
# ---------------------------------------------------------------------------


def _mesh_axes(mesh: Mesh) -> tuple[tuple[str, ...], str | None]:
    names = mesh.axis_names
    data_axes = tuple(a for a in ("pod", "data") if a in names)
    model_axis = "model" if "model" in names else None
    return data_axes, model_axis


def make_ctx(mesh: Mesh, cfg: TrainStepConfig | None = None) -> ParallelCtx:
    """The models' explicit-collective context.  With a ``cfg`` and a model
    axis the ctx carries the configured EP all-to-all (``moe_transport`` /
    ``moe_channels``) as its dispatch/combine primitive; without one the
    ctx falls back to the native tiled ``lax.all_to_all``."""
    data_axes, model_axis = _mesh_axes(mesh)
    moe_comm = build_moe_comm(mesh, cfg) if cfg is not None else None
    a2a = moe_comm.all_to_all if moe_comm is not None else None
    return ParallelCtx(model_axis=model_axis, data_axes=data_axes, a2a=a2a)


def build_moe_comm(mesh: Mesh, cfg: TrainStepConfig) -> Communicator | None:
    """The EP communicator :func:`make_ctx` attaches (None without a model
    axis) — the dry-run prices its :meth:`~repro.comm.Communicator.a2a_plan`
    against the lowered HLO."""
    _, model_axis = _mesh_axes(mesh)
    if model_axis is None:
        return None
    return Communicator(mesh, CommConfig(
        transport=cfg.moe_transport, data_axes=(model_axis,),
        channels=cfg.moe_channels))


def _sizes(mesh: Mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def _flat_spec(mesh: Mesh) -> P:
    return P(tuple(mesh.axis_names))


def build_comm(mesh: Mesh, cfg: TrainStepConfig, *,
               bucket_bytes: int | None = None) -> Communicator:
    """The step's communicator over the mesh's data axes."""
    data_axes, _ = _mesh_axes(mesh)
    ccfg = cfg.comm_config(data_axes)
    if bucket_bytes is not None:
        ccfg = replace(ccfg, bucket_bytes=bucket_bytes)
    return Communicator(mesh, ccfg)


def _local_shapes(tree_abs, specs, mesh: Mesh):
    """Per-device shapes given PartitionSpecs (all axes manual)."""
    sizes = _sizes(mesh)

    def shrink(leaf, spec):
        shape = list(leaf.shape)
        for d, ax in enumerate(spec):
            if ax is None:
                continue
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                shape[d] //= sizes[a]
        return jax.ShapeDtypeStruct(tuple(shape), leaf.dtype)

    return jax.tree.map(shrink, tree_abs, specs,
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def _slice_to_local(tree_full, specs):
    """Inside manual shard_map: slice full arrays down to this device's shard."""
    def one(leaf, spec):
        for d, ax in enumerate(spec):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            idx = jnp.zeros((), jnp.int32)
            p = 1
            for a in axes:
                idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
                p *= jax.lax.axis_size(a)
            seg = leaf.shape[d] // p
            leaf = jax.lax.dynamic_slice_in_dim(leaf, idx * seg, seg, axis=d)
        return leaf

    return jax.tree.map(one, tree_full, specs,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# norm-accounting weights: model-replicated fields must be counted once in
# the global grad norm, not model_size times (kv projections replicate)
# ---------------------------------------------------------------------------


def build_norm_weights(plan: BucketPlan, specs_flat: list, model_size: int
                       ) -> list[np.ndarray]:
    """Per-bucket fp32 weight vector: 1.0 on model-sharded fields,
    1/model_size on replicated fields (so a psum over the model axis counts
    each parameter exactly once)."""
    rep_w = 1.0 / max(model_size, 1)
    weights = [np.full((n,), rep_w, np.float32) for n in plan.bucket_sizes]
    for f in plan.fields:
        spec = specs_flat[f.leaf]
        sharded = any(MODEL_AXIS in (ax if isinstance(ax, tuple) else (ax,))
                      for ax in spec if ax is not None)
        if sharded:
            weights[f.bucket][f.offset:f.offset + f.size] = 1.0
    return weights


def build_span_norm_weights(layout: ArenaLayout,
                            bucket_weights: list[np.ndarray]
                            ) -> list[np.ndarray]:
    """Per-*span* norm weights for the arena ZeRO path: each span's vector
    is its member buckets' weights at their intra-span offsets, zero on the
    page padding (padding elements must never count in the grad norm)."""
    out = []
    for sp in layout.spans:
        w = np.zeros((sp.size,), np.float32)
        for b in sp.buckets:
            seg = layout.segment_of(b)
            off = seg.offset - sp.offset
            w[off:off + seg.size] = bucket_weights[b]
        out.append(w)
    return out


def _weighted_sq_sum(shard: jax.Array, w, axes: tuple[str, ...]
                    ) -> jax.Array:
    """``sum(shard² · w)`` over this rank's RS-shard of the per-bucket
    norm weights ``w``.  A weight vector that is one value throughout
    (every field equally model-replicated — always so without a model
    axis) is applied as that scalar: the dense vector would be a
    bucket-sized literal baked into the compiled step (GBs at published
    widths, and minutes of TPU compile)."""
    sq = jnp.square(shard.astype(jnp.float32))
    w = np.asarray(w)
    if w.size and (w == w.flat[0]).all():
        return jnp.sum(sq) * w.flat[0]
    return jnp.sum(sq * _slice_like_shard(jnp.asarray(w), axes))


def _slice_like_shard(w: jax.Array, axes: tuple[str, ...]) -> jax.Array:
    """Slice a per-bucket weight vector down to this rank's RS-shard, using
    the same ownership layout as hierarchical reduce-scatter (inner axis
    segments first)."""
    for ax in axes:
        p = jax.lax.axis_size(ax)
        r = jax.lax.axis_index(ax)
        seg = w.shape[0] // p
        w = jax.lax.dynamic_slice_in_dim(w, r * seg, seg)
    return w


# ---------------------------------------------------------------------------
# fsdp (ZeRO-3) planning
# ---------------------------------------------------------------------------


class FsdpPlan:
    """Per-group flat-bucket layout: every block (and each root entry) is
    bucketised separately so layers gather/release independently inside
    their remat boundary."""

    def __init__(self, model: Model, mesh: Mesh, cfg: TrainStepConfig):
        self.model = model
        self.mesh = mesh
        self.gather_impl = cfg.fsdp_gather
        data_axes, _ = _mesh_axes(mesh)
        self.data_axes = data_axes
        self.comm = build_comm(mesh, cfg, bucket_bytes=cfg.fsdp_bucket_bytes)
        if self.gather_impl == "ring" and not self.comm.spec.supports_rs:
            raise ValueError(
                f"fsdp_gather='ring' needs a transport with supports_rs; "
                f"{self.comm.cfg.transport!r} has none — use fsdp_gather="
                f"'native' or a ring transport")
        self.dp_world = self.comm.world
        self.bucketer = self.comm.bucketer
        self.pspecs = model.param_specs(mesh)
        local = _local_shapes(model.abstract_params(), self.pspecs, mesh)
        self.local_abs = local
        self.block_keys = [k for k in ("blocks", "enc_blocks", "dec_blocks")
                           if isinstance(local, dict) and k in local]
        self.groups: dict[str, Any] = {}
        for k in local:
            if k in self.block_keys:
                for i, blk in enumerate(local[k]):
                    self.groups[f"{k}.{i}"] = blk
            else:
                self.groups[f"root.{k}"] = local[k]
        self.plans = {name: self.bucketer.plan(tree)
                      for name, tree in self.groups.items()}
        # arena accumulation buffer: one segment per group-bucket *shard*,
        # in grads-tree leaf order (dicts flatten key-sorted); quantized
        # (int8 payload + scales + error feedback) under wire_codec
        self.arena_layout: ArenaLayout | QuantArenaLayout | None = None
        if cfg.use_arena:
            shard_sizes = [n // max(self.dp_world, 1)
                           for name in sorted(self.plans)
                           for n in self.plans[name].bucket_sizes]
            if self.comm.codec is not None:
                self.arena_layout = plan_quant_arena(
                    shard_sizes, page_bytes=self.comm.cfg.page_bytes,
                    block=self.comm.cfg.codec_block)
            else:
                self.arena_layout = plan_arena(
                    shard_sizes, page_bytes=self.comm.cfg.page_bytes,
                    dtype=jnp.float32)
        # static norm-accounting weights per group (model-replication aware)
        msize = _sizes(mesh).get("model", 1)
        self.norm_weights = {}
        for name in self.groups:
            spec_tree = self._group_of_tree(self.pspecs, name)
            sflat = jax.tree_util.tree_flatten(
                spec_tree, is_leaf=lambda x: isinstance(x, P))[0]
            self.norm_weights[name] = build_norm_weights(
                self.plans[name], sflat, msize)

    @staticmethod
    def _group_of_tree(tree, name):
        kind, _, idx = name.partition(".")
        if kind in ("blocks", "enc_blocks", "dec_blocks"):
            return tree[kind][int(idx)]
        return tree[idx]

    # inside manual shard_map -------------------------------------------------

    def shard_group(self, tree_local, name):
        """Local-model group tree -> flat shards over the data axes."""
        buckets, _ = self.bucketer.bucketize(tree_local, self.plans[name])
        out = []
        for b in buckets:
            for ax in reversed(self.data_axes):      # outermost segment first
                p = jax.lax.axis_size(ax)
                r = jax.lax.axis_index(ax)
                seg = b.shape[0] // p
                b = jax.lax.dynamic_slice_in_dim(b, r * seg, seg)
            out.append(b)
        return out

    def gather_group(self, shards, name, dtype=None):
        """Flat shards -> full group tree via all-gather over the data axes.

        ``native``: one XLA all-gather op per bucket per axis (transpose =
        psum_scatter).  ``ring``: our unrolled ppermute schedule (transpose
        == ring reduce-scatter-sum, verified) — exposes every hop to the
        scheduler/roofline at the cost of much larger HLO.
        """
        full = []
        for s in shards:
            if dtype is not None:
                s = s.astype(dtype)
            full.append(self.comm.gather_flat(
                s, native=self.gather_impl != "ring"))
        return self.bucketer.debucketize(full, self.plans[name],
                                         cast_to=dtype)

    def shard_state(self, params_local):
        groups = {}
        for name in self.groups:
            groups[name] = self.shard_group(self._group_of(params_local, name),
                                            name)
        return groups

    def _group_of(self, params, name):
        kind, _, idx = name.partition(".")
        if kind in ("blocks", "enc_blocks", "dec_blocks"):
            return params[kind][int(idx)]
        return params[idx]

    def params_and_resolver(self, groups, dtype):
        """Root groups gathered eagerly; blocks left as shard lists with a
        resolver the model calls inside each layer's remat boundary."""
        params: dict = {}
        for name, shards in groups.items():
            kind, _, idx = name.partition(".")
            if kind == "root":
                params[idx] = self.gather_group(shards, name, dtype)
        for k in self.block_keys:
            n = len([1 for name in groups if name.startswith(k + ".")])
            params[k] = [groups[f"{k}.{i}"] for i in range(n)]

        def resolver(kind: str, i: int, shards):
            return self.gather_group(shards, f"{kind}.{i}", dtype)

        return params, resolver


# ---------------------------------------------------------------------------
# state init
# ---------------------------------------------------------------------------


def init_train_state(model: Model, mesh: Mesh, cfg: TrainStepConfig,
                     key=None, abstract: bool = False):
    """Returns (state, state_specs).  ``abstract=True`` -> ShapeDtypeStructs."""
    pspecs = model.param_specs(mesh)
    flat = _flat_spec(mesh)
    key = key if key is not None else jax.random.key(0)

    # use_arena: the persistent page-aligned comm buffer lives in the state
    # (one flat leaf, donated with the rest), so every step reuses the same
    # allocation — the paper's allocate-once registration.  Under
    # wire_codec='int8' the arena leaf is the int8 payload+scale buffer and
    # an fp32 "ef" leaf carries the error-feedback residuals; both donated,
    # both restored by path (ckpt.restore keeps the fresh zeros when a
    # checkpoint written without them is loaded).
    arena_elems = 0
    arena_dtype = jnp.float32
    ef_elems = 0

    def _arena_leaves(state):
        state["arena"] = jnp.zeros((arena_elems,), arena_dtype)
        if ef_elems:
            state["ef"] = jnp.zeros((ef_elems,), jnp.float32)
        return state

    def _arena_specs(specs, layout):
        nonlocal arena_elems, arena_dtype, ef_elems
        arena_elems = layout.total_elems
        arena_dtype = jnp.dtype(layout.dtype)
        specs["arena"] = flat
        if isinstance(layout, QuantArenaLayout):
            ef_elems = layout.payload_elems
            specs["ef"] = flat

    if cfg.dp_mode == "replicated":
        specs = {"params": pspecs, "opt": {"mu": pspecs, "nu": pspecs},
                 "step": P()}
        if cfg.use_arena:
            comm = build_comm(mesh, cfg)
            local = _local_shapes(model.abstract_params(), pspecs, mesh)
            _arena_specs(specs, comm.arena_layout(local))

        def mk(k):
            p_local = _slice_to_local(model.init(k), pspecs)
            state = {"params": p_local, "opt": init_opt_state(p_local),
                     "step": jnp.zeros((), jnp.int32)}
            return _arena_leaves(state) if cfg.use_arena else state

    elif cfg.dp_mode == "zero1":
        comm = build_comm(mesh, cfg)
        local = _local_shapes(model.abstract_params(), pspecs, mesh)
        plan = comm.bucketer.plan(local)
        if cfg.use_arena:
            # optimizer shards follow the fused-span layout, not the buckets
            layout = comm.arena_layout(local)
            shard_sizes = [sp.size // comm.world for sp in layout.spans]
        else:
            shard_sizes = [n // comm.world for n in plan.bucket_sizes]
        specs = {"params": pspecs,
                 "opt": {"mu": [flat] * len(shard_sizes),
                         "nu": [flat] * len(shard_sizes)},
                 "step": P()}
        if cfg.use_arena:
            _arena_specs(specs, layout)

        def mk(k):
            p_local = _slice_to_local(model.init(k), pspecs)
            zeros = lambda: [jnp.zeros((n,), jnp.float32) for n in shard_sizes]
            state = {"params": p_local, "opt": {"mu": zeros(), "nu": zeros()},
                     "step": jnp.zeros((), jnp.int32)}
            return _arena_leaves(state) if cfg.use_arena else state

    elif cfg.dp_mode == "fsdp":
        plan = FsdpPlan(model, mesh, cfg)
        spec_groups = {name: [flat] * plan.plans[name].n_buckets
                       for name in plan.groups}
        specs = {"groups": spec_groups,
                 "opt": {"mu": spec_groups, "nu": spec_groups},
                 "step": P()}
        if cfg.use_arena:
            _arena_specs(specs, plan.arena_layout)

        def mk(k):
            p_local = _slice_to_local(model.init(k), pspecs)
            groups = plan.shard_state(p_local)
            zeros = lambda: jax.tree.map(
                lambda s: jnp.zeros_like(s, jnp.float32), groups)
            state = {"groups": groups, "opt": {"mu": zeros(), "nu": zeros()},
                     "step": jnp.zeros((), jnp.int32)}
            return _arena_leaves(state) if cfg.use_arena else state

    else:
        raise ValueError(f"dp_mode must be one of {DP_MODES}")

    def mk_from_data(kd):
        return mk(jax.random.wrap_key_data(kd))

    fn = jax.shard_map(mk_from_data, mesh=mesh, in_specs=P(),
                       out_specs=specs, check_vma=False)
    if abstract:
        kd_abs = jax.eval_shape(jax.random.key_data, jax.random.key(0))
        return jax.eval_shape(fn, kd_abs), specs
    return jax.jit(fn)(jax.random.key_data(key)), specs


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------


def build_step_schedule(model: Model, mesh: Mesh, cfg: TrainStepConfig
                        ) -> CommSchedule:
    """The :class:`CommSchedule` the step executes (also what the dry-run
    records and the roofline's overlap fraction reads).

    ``replicated`` / ``zero1`` derive issue slots from the communicator's
    bucket layout of the local gradient tree — span-level
    (:meth:`~repro.comm.Communicator.arena_schedule`) when ``use_arena``
    fuses each channel's contiguous arena span into one collective.
    ``fsdp`` always reports the ``scheduled`` readiness model regardless of
    the configured policy: its reduce-scatter is the autodiff transpose of
    the per-layer weight gather, so streaming in backward readiness order
    is *intrinsic* — the schedule policy only shapes local shard
    accumulation, never serialises comm.
    """
    policy = cfg.schedule_policy
    m = cfg.microbatches
    if cfg.dp_mode == "fsdp":
        return _fsdp_schedule(FsdpPlan(model, mesh, cfg), m)
    comm = build_comm(mesh, cfg)
    pspecs = model.param_specs(mesh)
    local = _local_shapes(model.abstract_params(), pspecs, mesh)
    if cfg.use_arena:
        return comm.arena_schedule(local, policy, m)
    return comm.schedule(local, policy, m)


def _fsdp_schedule(plan: FsdpPlan, microbatches: int) -> CommSchedule:
    sizes = [n for name in sorted(plan.plans)
             for n in plan.plans[name].bucket_sizes]
    return build_schedule("scheduled", sizes, microbatches=microbatches,
                          channels=plan.comm.cfg.channels)


def build_train_step(model: Model, mesh: Mesh, cfg: TrainStepConfig,
                     batch_pspecs, donate: bool = True):
    """Returns ``step(state, batch) -> (state, metrics)`` jitted over the
    fully-manual mesh."""
    pspecs = model.param_specs(mesh)
    ctx = make_ctx(mesh, cfg)
    schedule = make_schedule(cfg.optim.schedule, base_lr=cfg.optim.base_lr,
                             warmup=cfg.optim.warmup,
                             total=cfg.optim.total_steps)
    _, state_specs = init_train_state(model, mesh, cfg, abstract=True)
    metric_specs = {"loss": P(), "grad_norm": P(), "lr": P(),
                    "moe_drop_fraction": P()}

    if cfg.dp_mode in ("replicated", "zero1"):
        comm = build_comm(mesh, cfg)
        local_abs = _local_shapes(model.abstract_params(), pspecs, mesh)
        # single source with the dry-run's prediction: the schedule the step
        # executes IS the one build_step_schedule reports (span-level when
        # the arena fuses each channel into one collective)
        comm_sched = build_step_schedule(model, mesh, cfg)
        comm_arena = comm.arena(local_abs) if cfg.use_arena else None
        zero1_norm_weights = None
        if cfg.dp_mode == "zero1":
            if not comm.spec.supports_rs:
                raise ValueError(
                    f"dp_mode='zero1' needs a transport with supports_rs; "
                    f"{comm.cfg.transport!r} has none (registered ring "
                    f"transports do)")
            z1_plan = comm.bucketer.plan(local_abs)
            specs_flat = jax.tree_util.tree_flatten(
                pspecs, is_leaf=lambda x: isinstance(x, P))[0]
            zero1_norm_weights = build_norm_weights(
                z1_plan, specs_flat, _sizes(mesh).get("model", 1))
            if comm_arena is not None:
                # shards follow the fused spans; padding weighs zero
                zero1_norm_weights = build_span_norm_weights(
                    comm_arena.layout, zero1_norm_weights)

        def step_fn(state, batch):
            drops: list = []             # per-microbatch moe_drop_fraction

            def gfn(p, mb):
                stats: list = []
                loss = model.loss_fn(p, mb, ctx=ctx,
                                     causal_skip=cfg.causal_skip,
                                     stats_out=stats)
                drop = (stats[0]["moe_drop_fraction"] if stats
                        else jnp.zeros((), jnp.float32))
                return loss, drop

            def grad_fn(p, mb):
                (loss, drop), g = jax.value_and_grad(gfn, has_aux=True)(p, mb)
                drops.append(drop)
                return loss, g

            new_arena = None
            new_ef = None
            quant = isinstance(comm_arena, QuantCommArena)
            if cfg.dp_mode == "replicated":
                if quant:
                    loss, (grads, new_arena, new_ef) = comm.reduce_scheduled(
                        grad_fn, state["params"], batch, comm_sched,
                        op="all_reduce", arena=comm_arena,
                        arena_buf=state["arena"], ef_buf=state["ef"])
                elif comm_arena is not None:
                    loss, (grads, new_arena) = comm.reduce_scheduled(
                        grad_fn, state["params"], batch, comm_sched,
                        op="all_reduce", arena=comm_arena,
                        arena_buf=state["arena"])
                else:
                    loss, grads = comm.reduce_scheduled(
                        grad_fn, state["params"], batch, comm_sched,
                        op="all_reduce")
                gnorm = global_grad_norm(grads, pspecs, ctx)
                factor = clip_factor(gnorm, cfg.optim.clip_norm)
                grads = jax.tree.map(lambda g: g * factor, grads)
                lr = schedule(state["step"])
                new_p, new_opt = adamw_tree_update(
                    state["params"], grads, state["opt"], state["step"], lr,
                    cfg.optim)
                new_state = {"params": new_p, "opt": new_opt,
                             "step": state["step"] + 1}
            else:  # zero1: buckets reduce-scatter as their microbatch's
                   # backward finishes (streamed ZeRO); shards accumulate
                if quant:
                    loss, (shards, plan, new_arena, new_ef) = (
                        comm.reduce_scheduled(
                            grad_fn, state["params"], batch, comm_sched,
                            op="reduce_scatter", arena=comm_arena,
                            arena_buf=state["arena"], ef_buf=state["ef"]))
                elif comm_arena is not None:
                    loss, (shards, plan, new_arena) = comm.reduce_scheduled(
                        grad_fn, state["params"], batch, comm_sched,
                        op="reduce_scatter", arena=comm_arena,
                        arena_buf=state["arena"])
                else:
                    loss, (shards, plan) = comm.reduce_scheduled(
                        grad_fn, state["params"], batch, comm_sched,
                        op="reduce_scatter")
                # exact global norm over the *reduced* gradient: weight
                # model-replicated fields by 1/model_size before the psum
                ordered = comm.ordered_axes
                sq = jnp.zeros((), jnp.float32)
                for s, w in zip(shards, zero1_norm_weights):
                    sq = sq + _weighted_sq_sum(s, w, ordered)
                gnorm = jnp.sqrt(ctx.psum(ctx.psum_data(sq)))
                factor = clip_factor(gnorm, cfg.optim.clip_norm)
                shards = [s * factor for s in shards]
                lr = schedule(state["step"])
                deltas, new_opt = adamw_flat_update(shards, state["opt"],
                                                    state["step"], lr,
                                                    cfg.optim)
                if comm_arena is not None:
                    spans = comm.all_gather(deltas)
                    delta_tree = comm.bucketer.debucketize(
                        comm_arena.unpack_spans(spans), plan)
                else:
                    delta_tree = comm.all_gather_buckets(deltas, plan)
                wd = 1 - lr * cfg.optim.weight_decay
                new_p = jax.tree.map(
                    lambda p, d: (p.astype(jnp.float32) * wd
                                  + d.astype(jnp.float32)).astype(p.dtype),
                    state["params"], delta_tree)
                new_state = {"params": new_p, "opt": new_opt,
                             "step": state["step"] + 1}
            if new_arena is not None:
                new_state["arena"] = new_arena
            if new_ef is not None:
                new_state["ef"] = new_ef
            drop = sum(drops) / max(len(drops), 1)
            metrics = {"loss": ctx.pmean_data(loss), "grad_norm": gnorm,
                       "lr": lr, "moe_drop_fraction": ctx.pmean_data(drop)}
            return new_state, metrics

    else:  # fsdp / ZeRO-3
        plan = FsdpPlan(model, mesh, cfg)
        gdt = jnp.dtype(cfg.gather_dtype)
        # reduction rides the autodiff transpose of the per-layer gather, so
        # streaming in readiness order is intrinsic; the schedule records it
        comm_sched = _fsdp_schedule(plan, cfg.microbatches)
        fsdp_impl = ("pallas" if plan.comm.cfg.local_op == "pallas"
                     else "jnp")
        fsdp_arena = None
        if cfg.use_arena:
            fsdp_arena = (QuantCommArena(plan.arena_layout, impl=fsdp_impl)
                          if isinstance(plan.arena_layout, QuantArenaLayout)
                          else CommArena(plan.arena_layout, impl=fsdp_impl))

        def step_fn(state, batch):
            drops: list = []             # per-microbatch moe_drop_fraction

            def gfn(groups, mb):
                params, resolver = plan.params_and_resolver(groups, gdt)
                stats: list = []
                loss = model.loss_fn(params, mb, ctx=ctx,
                                     causal_skip=cfg.causal_skip,
                                     block_resolver=resolver,
                                     stats_out=stats)
                drop = (stats[0]["moe_drop_fraction"] if stats
                        else jnp.zeros((), jnp.float32))
                return loss, drop

            def grad_fn(groups, mb):
                (loss, drop), g = jax.value_and_grad(gfn, has_aux=True)(
                    groups, mb)
                drops.append(drop)
                return loss, g

            new_arena = None
            new_ef = None
            if isinstance(fsdp_arena, QuantCommArena):
                # quantized accumulation buffer: pack+quantize with error
                # feedback once per step, fused dequant+unpack out
                loss, (grads, new_arena, new_ef) = plan.comm.reduce_scheduled(
                    grad_fn, state["groups"], batch, comm_sched, op="none",
                    arena=fsdp_arena, arena_buf=state["arena"],
                    ef_buf=state["ef"])
            elif fsdp_arena is not None:
                # the arena is the microbatch accumulation buffer (grads
                # arrive pre-sharded via the gather transpose)
                loss, (grads, new_arena) = plan.comm.reduce_scheduled(
                    grad_fn, state["groups"], batch, comm_sched, op="none",
                    arena=fsdp_arena, arena_buf=state["arena"])
            else:
                loss, grads = plan.comm.reduce_scheduled(
                    grad_fn, state["groups"], batch, comm_sched, op="none")
            # grads are flat shards already (AG-transpose == RS-sum over the
            # data axes); normalise the sum into a mean.
            inv = 1.0 / max(plan.dp_world, 1)
            grads = jax.tree.map(lambda g: g * inv, grads)
            ordered = tuple(reversed(plan.data_axes))
            sq = jnp.zeros((), jnp.float32)
            for name in sorted(plan.groups):
                for g, w in zip(grads[name], plan.norm_weights[name]):
                    sq = sq + _weighted_sq_sum(g, w, ordered)
            gnorm = jnp.sqrt(ctx.psum(ctx.psum_data(sq)))
            factor = clip_factor(gnorm, cfg.optim.clip_norm)
            lr = schedule(state["step"])
            wd = 1 - lr * cfg.optim.weight_decay
            new_groups, new_mu, new_nu = {}, {}, {}
            for name in state["groups"]:
                gsh = [g * factor for g in grads[name]]
                deltas, nopt = adamw_flat_update(
                    gsh, {"mu": state["opt"]["mu"][name],
                          "nu": state["opt"]["nu"][name]},
                    state["step"], lr, cfg.optim)
                new_groups[name] = [
                    (p.astype(jnp.float32) * wd + d).astype(p.dtype)
                    for p, d in zip(state["groups"][name], deltas)]
                new_mu[name] = nopt["mu"]
                new_nu[name] = nopt["nu"]
            new_state = {"groups": new_groups,
                         "opt": {"mu": new_mu, "nu": new_nu},
                         "step": state["step"] + 1}
            if new_arena is not None:
                new_state["arena"] = new_arena
            if new_ef is not None:
                new_state["ef"] = new_ef
            drop = sum(drops) / max(len(drops), 1)
            metrics = {"loss": ctx.pmean_data(loss), "grad_norm": gnorm,
                       "lr": lr, "moe_drop_fraction": ctx.pmean_data(drop)}
            return new_state, metrics

    sharded = jax.shard_map(step_fn, mesh=mesh,
                            in_specs=(state_specs, batch_pspecs),
                            out_specs=(state_specs, metric_specs),
                            check_vma=False)
    return jax.jit(sharded, donate_argnums=(0,) if donate else ())

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: AOT lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: sharding
mismatches, compile-time OOM and unsupported collectives all fail here.
Per cell it records memory_analysis (fits 16 GB?), cost_analysis
(FLOPs/bytes) and the parsed collective wire bytes -> the three roofline
terms of EXPERIMENTS.md §Roofline.

The two XLA_FLAGS lines above MUST run before any other import: jax locks
the device count at first initialisation.

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --arch all --mesh both \
        --out experiments/dryrun.json
"""

import argparse
import json
import time
import traceback

from dataclasses import replace

import jax
import jax.numpy as jnp

from repro.comm import SCHEDULE_POLICIES
from repro.configs import SHAPES, applicable_shapes, get_config, list_archs
from repro.data import make_batch_specs
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import (Roofline, collective_wire_bytes,
                                   model_flops_estimate)
from repro.launch.settings import settings_for
from repro.models import build_model
from repro.runtime.serve_step import build_decode_step, build_prefill
from repro.runtime.train_step import (TrainStepConfig, build_step_schedule,
                                      build_train_step, init_train_state)

HBM_PER_CHIP = 16 * 2**30

# canonical implementation lives in repro.tune.db (jax-free, shared with the
# tuning-DB keys); re-exported here because this is where cache-key users
# have always imported it from.  Folded into the cache key by
# :func:`cell_key` so that re-running with a different ``--accum-policy`` /
# schedule / solver override can never be served a stale cached cell.
from repro.tune.db import overrides_fingerprint  # noqa: E402  (re-export)


def _tuned_pricing(db, *, arch: str, mesh_label: str, transport: str,
                   channels: int | None = None,
                   page_bytes: int | None = None) -> dict | None:
    """Measured pricing for one dry-run cell from a tuning DB.

    Returns ``None`` when no record matches the cell's transport (fitted
    constants never transfer across schedules); otherwise a dict with the
    rebuilt :class:`~repro.comm.plan.LatencyModel`, the winning record's
    key, and the ``model_error`` block (the fit's predicted-vs-measured
    residuals) the cell record surfaces."""
    from repro.comm.plan import LatencyModel
    from repro.tune.db import model_error_summary

    hit = db.lookup(transport=transport, arch=arch, mesh=mesh_label,
                    channels=channels, page_bytes=page_bytes)
    if hit is None:
        return None
    key, rec = hit
    model = LatencyModel.from_record(rec)
    return {"model": model, "key": key,
            "alpha_s": model.alpha_s, "bandwidth": model.bandwidth,
            "model_error": model_error_summary(rec)}


def cell_key(tag: str, arch: str, shape: str, mesh_label: str,
             overrides: dict | None = None) -> str:
    """Cache key of one dry-run cell in the output JSON."""
    base = f"{tag}|{arch}|{shape}|{mesh_label}"
    fp = overrides_fingerprint(overrides)
    return f"{base}|ov[{fp}]" if fp else base


def _abstract_batch(model, shape_cfg):
    return model.input_specs(shape_cfg)


def make_step_config(arch: str, overrides: dict | None = None) -> TrainStepConfig:
    """Per-arch step config with override plumbing.

    ``comm_<field>`` keys hit :class:`~repro.comm.CommConfig` directly
    (``comm_page_bytes`` included); every other key is a
    :class:`TrainStepConfig` field (``microbatches``, ``schedule``,
    ``use_arena``, ``dp_mode``, ...).  Legacy ``accum_microbatches`` /
    ``accum_policy`` spellings map onto the new fields, with the
    new-style key winning when both are present.  (The old ``reduce_*``
    string-policy overrides are gone with the ``core.overlap`` shim —
    use ``comm_transport`` etc.)
    """
    st = settings_for(arch)
    ccfg = st.comm_config()
    kw = dict(dp_mode=st.dp_mode, microbatches=st.microbatches,
              schedule="accumulate_then_reduce", causal_skip=False,
              moe_transport=st.moe_transport, moe_channels=st.moe_channels)
    if overrides:
        stale = [k for k in overrides if k.startswith("reduce_")]
        if stale:
            raise ValueError(
                f"reduce_* overrides were removed with the string-policy "
                f"shim; use comm_<field> (e.g. comm_transport, "
                f"comm_wire_dtype) — got {stale}")
        # new-style comm_* keys hit CommConfig fields directly
        comm_over = {k[5:]: v for k, v in overrides.items()
                     if k.startswith("comm_")}
        rest = {k: v for k, v in overrides.items()
                if not k.startswith("comm_")}
        rest.setdefault("microbatches", rest.pop("accum_microbatches", None))
        rest.setdefault("schedule", rest.pop("accum_policy", None))
        rest = {k: v for k, v in rest.items() if v is not None}
        if comm_over:
            ccfg = replace(ccfg, **comm_over)
        kw.update(rest)
    return TrainStepConfig(comm=ccfg, **kw)


def comm_plan_summary(model, mesh, tcfg: TrainStepConfig) -> dict:
    """The :class:`repro.comm.CommPlan` the step will execute, as JSON —
    the dry-run report and the benchmarks read the same object.

    For fsdp the step buckets per parameter group with
    ``fsdp_bucket_bytes`` (see :class:`FsdpPlan`), so the summary
    aggregates one CommPlan per group rather than pretending the whole
    tree rides one plan."""
    from repro.runtime.train_step import FsdpPlan, _local_shapes, build_comm

    if tcfg.dp_mode == "fsdp":
        fplan = FsdpPlan(model, mesh, tcfg)
        plans = [fplan.comm.plan(tree) for tree in fplan.groups.values()]
        head = plans[0].describe()
        return {
            "transport": head["transport"],
            "axes": head["axes"], "axis_sizes": head["axis_sizes"],
            "world": head["world"],
            "n_groups": len(plans),
            "n_buckets": sum(p.n_buckets for p in plans),
            "total_elems": sum(p.total_elems for p in plans),
            "n_channels": head["n_channels"],
            "bytes_per_device": sum(p.bytes_per_device for p in plans),
            "grad_bytes": sum(
                p.predicted_collective_bytes()["grad_bytes"] for p in plans),
            "channel_imbalance": max(p.channel_imbalance for p in plans),
        }
    comm = build_comm(mesh, tcfg)
    pspecs = model.param_specs(mesh)
    local = _local_shapes(model.abstract_params(), pspecs, mesh)
    return comm.plan(local).describe()


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               overrides: dict | None = None):
    """Returns (lowered, n_devices, model, shape_cfg, kind)."""
    cfg = get_config(arch)
    model = build_model(cfg)
    shape_cfg = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    st = settings_for(arch)

    with mesh:
        if shape_cfg.kind == "train":
            tcfg = make_step_config(arch, overrides)
            batch_specs = make_batch_specs(model.cfg, shape_cfg, mesh)
            step = build_train_step(model, mesh, tcfg, batch_specs,
                                    donate=True)
            state_abs, _ = init_train_state(model, mesh, tcfg, abstract=True)
            batch_abs = _abstract_batch(model, shape_cfg)
            lowered = step.lower(state_abs, batch_abs)
        elif shape_cfg.kind == "prefill":
            wm = st.serve_weights
            if overrides and "serve_weights" in overrides:
                wm = overrides["serve_weights"]
            step, pspecs = build_prefill(model, mesh, shape_cfg,
                                         weight_mode=wm)
            params_abs = _abstract_serve_params(model, mesh, wm)
            batch_abs = _abstract_batch(model, shape_cfg)
            lowered = step.lower(params_abs, batch_abs)
        else:  # decode
            wm = st.serve_weights
            if overrides and "serve_weights" in overrides:
                wm = overrides["serve_weights"]
            step, pspecs, _ = build_decode_step(model, mesh, shape_cfg,
                                                weight_mode=wm)
            params_abs = _abstract_serve_params(model, mesh, wm)
            b = shape_cfg.global_batch
            token = jax.ShapeDtypeStruct((b,), jnp.int32)
            state_abs = model.abstract_decode_state(b, shape_cfg.seq_len)
            pos = jax.ShapeDtypeStruct((), jnp.int32)
            lowered = step.lower(params_abs, token, state_abs, pos)
    return lowered, n_dev, model, shape_cfg


def _abstract_serve_params(model, mesh, weight_mode):
    if weight_mode == "gathered":
        from repro.runtime.train_step import FsdpPlan, TrainStepConfig as TSC

        plan = FsdpPlan(model, mesh, TSC(dp_mode="fsdp"))
        n_dev = mesh.devices.size
        # local shard length is n // dp_world; global flat = local * n_devices
        groups = {name: [jax.ShapeDtypeStruct((n // plan.dp_world * n_dev,),
                                              jnp.float32)
                         for n in p.bucket_sizes]
                  for name, p in plan.plans.items()}
        return {"groups": groups}
    return model.abstract_params()


def _model_size(mesh) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)


def analyse(lowered, n_dev: int, model, shape_cfg,
            overlap_fraction: float = 0.0, latency=None) -> dict:
    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    txt = compiled.as_text()
    stats = collective_wire_bytes(txt)

    tokens = shape_cfg.global_batch * (shape_cfg.seq_len
                                       if shape_cfg.kind != "decode" else 1)
    n_active = model.active_param_count()
    mf = model_flops_estimate(n_active, tokens, shape_cfg.kind)
    roof_kw = dict(
        flops_per_device=float(ca.get("flops", 0.0)),
        hbm_bytes_per_device=float(ca.get("bytes accessed", 0.0)),
        wire_bytes_per_device=stats.wire_bytes,
        model_flops=mf,
        overlap_fraction=overlap_fraction,
        messages_per_device=stats.messages,
    )
    # --tuned: price the collective term with measured α/bandwidth
    roof = (Roofline.from_latency(latency, **roof_kw) if latency is not None
            else Roofline(**roof_kw))
    mem = {
        "argument_gb": ma.argument_size_in_bytes / 2**30,
        "output_gb": ma.output_size_in_bytes / 2**30,
        "temp_gb": ma.temp_size_in_bytes / 2**30,
        "alias_gb": ma.alias_size_in_bytes / 2**30,
    }
    # donated inputs alias outputs: live = args + temp (+ non-aliased out)
    live = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + max(ma.output_size_in_bytes - ma.alias_size_in_bytes, 0))
    mem["live_gb"] = live / 2**30
    mem["fits_16gb"] = bool(live <= HBM_PER_CHIP)
    return {
        "compile_s": compile_s,
        "memory": mem,
        "roofline": roof.as_dict(n_dev),
        "collectives": {"counts": stats.op_counts,
                        "bytes": stats.op_bytes,
                        "while_loops": stats.while_loops},
        "params": model.param_count(),
        "active_params": n_active,
    }


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             overrides: dict | None = None, tuned_db=None) -> dict:
    lowered, n_dev, model, shape_cfg = lower_cell(arch, shape_name, multi_pod,
                                                  overrides)
    mesh_label = "2x16x16" if multi_pod else "16x16"
    sched = None
    if shape_cfg.kind == "train":
        # the issue schedule the step executes: its overlap fraction makes
        # the roofline honest about compute/comm overlap
        mesh = make_production_mesh(multi_pod=multi_pod)
        tcfg = make_step_config(arch, overrides)
        with mesh:
            sched = build_step_schedule(model, mesh, tcfg)
    pricing = None
    if tuned_db is not None:
        if shape_cfg.kind == "train":
            tr, ch = tcfg.comm.transport, tcfg.comm.channels
        else:
            st = settings_for(arch)
            tr, ch = st.transport, st.channels
        pricing = _tuned_pricing(tuned_db, arch=arch, mesh_label=mesh_label,
                                 transport=tr, channels=ch)
    out = analyse(lowered, n_dev, model, shape_cfg,
                  overlap_fraction=sched.overlap_fraction if sched else 0.0,
                  latency=pricing["model"] if pricing else None)
    if shape_cfg.kind == "train":
        with mesh:
            out["comm_plan"] = comm_plan_summary(model, mesh, tcfg)
        out["schedule"] = sched.describe()
    if pricing:
        out["tuned"] = {"key": pricing["key"], "alpha_s": pricing["alpha_s"],
                        "bandwidth": pricing["bandwidth"]}
        out["model_error"] = pricing["model_error"]
    out.update({"arch": arch, "shape": shape_name,
                "mesh": mesh_label,
                "devices": n_dev})
    return out


MEM_DEFAULT_ARCHS = ["whisper-base", "llama3.2-1b"]


def _entry_param_elems(hlo_text: str, index: int, dtype: str = "f32"
                       ) -> int | None:
    """Element count of ENTRY parameter ``index`` in optimized HLO text —
    the *lowered* size of a buffer we predicted (fusion-internal
    ``parameter(i)`` lines outside ENTRY are ignored)."""
    import re as _re

    in_entry = False
    pat = _re.compile(rf"{dtype}\[(\d+)\]")
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY"):
            in_entry = True
            continue
        if in_entry:
            if line.startswith("}"):
                break
            if f"parameter({index})" in line:
                m = pat.search(line)
                return int(m.group(1)) if m else None
    return None


def run_mem_cell(arch: str, page_bytes: int, bucket_mb: float, *,
                 channels: int = 2, transport: str = "psum",
                 tuned_db=None) -> dict:
    """One ``--suite mem`` cell: lower + compile a pack→reduce→unpack step
    over the arch's (reduced) gradient tree with a **donated** arena, then
    hold the :mod:`repro.mem` prediction layer to the optimized HLO with
    zero tolerance:

    * **bytes/pages** — the per-device arena parameter in the compiled
      module must be exactly ``ArenaLayout.total_elems`` fp32 elements
      (page-quantized), i.e. predicted bytes == lowered buffer size and
      predicted page count == lowered bytes / page_bytes;
    * **counts** — the arena path must lower to exactly ``n_spans``
      all-reduce ops (fused segments) and the per-bucket baseline to
      exactly ``n_buckets`` — strictly more whenever fusing collapses
      anything, the paper's fewer-larger-messages claim in HLO;
    * **wire bytes** — parsed collective bytes must equal
      ``CommPlan.arena_bytes_per_device`` (page padding crosses the wire;
      the roofline folds it via ``padding_wire_bytes_per_device``).
    """
    import jax.numpy as jnp
    from jax.sharding import AxisType, PartitionSpec as P
    from repro.comm import CommConfig
    from repro.configs import reduced_config
    from repro.runtime.train_step import _local_shapes, build_comm

    mesh = jax.make_mesh((4, 1), ("data", "model"),
                         devices=jax.devices()[:4],
                         axis_types=(AxisType.Auto,) * 2)
    n_dev = 4
    model = build_model(reduced_config(arch))
    tcfg = TrainStepConfig(
        dp_mode="replicated",
        comm=CommConfig(transport=transport, channels=channels,
                        bucket_bytes=int(bucket_mb * 2**20),
                        page_bytes=int(page_bytes)),
        schedule="scheduled", use_arena=True)
    with mesh:
        comm = build_comm(mesh, tcfg)
        pspecs = model.param_specs(mesh)
        local = _local_shapes(model.abstract_params(), pspecs, mesh)
        cplan = comm.plan(local)
        layout = cplan.arena_layout
        arena = comm.arena(local)
        sched_bucket = comm.schedule(local, "scheduled", 1)
        sched_arena = comm.arena_schedule(local, "scheduled", 1)
        grads_abs = model.abstract_params()
        batch_abs = {"x": jax.ShapeDtypeStruct((1,), jnp.float32)}

        def grad_like(p, mb):
            return jnp.zeros((), jnp.float32), p

        def arena_fn(buf, grads, batch):
            _, (tree, out) = comm.reduce_scheduled(
                grad_like, grads, batch, sched_arena, op="all_reduce",
                arena=arena, arena_buf=buf)
            return out, tree

        def bucket_fn(grads, batch):
            _, tree = comm.reduce_scheduled(grad_like, grads, batch,
                                            sched_bucket, op="all_reduce")
            return tree

        flat = P(tuple(mesh.axis_names))
        arena_abs = jax.ShapeDtypeStruct((n_dev * layout.total_elems,),
                                         jnp.float32)
        fa = jax.jit(jax.shard_map(
            arena_fn, mesh=mesh, in_specs=(flat, pspecs, P()),
            out_specs=(flat, pspecs), check_vma=False), donate_argnums=(0,))
        fb = jax.jit(jax.shard_map(
            bucket_fn, mesh=mesh, in_specs=(pspecs, P()),
            out_specs=pspecs, check_vma=False))
        t0 = time.time()
        ca = fa.lower(arena_abs, grads_abs, batch_abs).compile()
        cb = fb.lower(grads_abs, batch_abs).compile()
        compile_s = time.time() - t0

    txt_a, txt_b = ca.as_text(), cb.as_text()
    stats_a = collective_wire_bytes(txt_a)
    stats_b = collective_wire_bytes(txt_b)
    n_ar_arena = stats_a.op_counts.get("all-reduce", 0)
    n_ar_bucket = stats_b.op_counts.get("all-reduce", 0)

    # --- the zero-tolerance prediction checks -----------------------------
    # the arena is arena_fn's first (donated) argument -> ENTRY parameter 0
    # of the partitioned module; its lowered size must equal the predicted
    # page-quantized layout exactly
    lowered_elems = _entry_param_elems(txt_a, 0)
    if lowered_elems != layout.total_elems:
        raise AssertionError(
            f"lowered arena parameter is f32[{lowered_elems}], predicted "
            f"f32[{layout.total_elems}] ({layout.total_bytes} B, "
            f"{layout.n_pages} pages)")
    if n_ar_arena != layout.n_spans:
        raise AssertionError(
            f"arena path lowered to {n_ar_arena} all-reduce ops, predicted "
            f"{layout.n_spans} fused spans")
    if n_ar_bucket != cplan.n_buckets:
        raise AssertionError(
            f"bucket baseline lowered to {n_ar_bucket} all-reduce ops, "
            f"predicted {cplan.n_buckets} buckets")
    if layout.n_spans < cplan.n_buckets and not n_ar_arena < n_ar_bucket:
        raise AssertionError(
            f"fused spans did not reduce the collective count: "
            f"{n_ar_arena} vs {n_ar_bucket}")
    measured = stats_a.op_bytes.get("all-reduce", 0.0)
    predicted = cplan.arena_bytes_per_device
    if predicted and abs(measured - predicted) / predicted > 1e-9:
        raise AssertionError(
            f"arena wire bytes: predicted {predicted}, HLO {measured}")

    pricing = None
    if tuned_db is not None:
        pricing = _tuned_pricing(tuned_db, arch=arch, mesh_label="4x1",
                                 transport=transport, channels=channels,
                                 page_bytes=int(page_bytes))
    padding_wire = predicted * layout.padding_fraction
    roof_kw = dict(
        flops_per_device=0.0, hbm_bytes_per_device=0.0,
        wire_bytes_per_device=predicted - padding_wire,
        padding_wire_bytes_per_device=padding_wire,
        messages_per_device=cplan.arena_messages_per_device,
        overlap_fraction=sched_arena.overlap_fraction,
    )
    roof = (Roofline.from_latency(pricing["model"], **roof_kw)
            if pricing else Roofline(**roof_kw))
    tuned_extra = ({"tuned": {"key": pricing["key"],
                              "alpha_s": pricing["alpha_s"],
                              "bandwidth": pricing["bandwidth"]},
                    "model_error": pricing["model_error"]}
                   if pricing else {})
    return tuned_extra | {
        "arch": arch, "suite": "mem",
        "page_bytes": int(page_bytes),
        "bucket_mb": bucket_mb,
        "channels": channels,
        "transport": transport,
        "mesh": "4x1",
        "devices": n_dev,
        "compile_s": compile_s,
        "predicted_arena_bytes": layout.total_bytes,
        "predicted_arena_pages": layout.n_pages,
        "lowered_arena_elems": lowered_elems,
        "arena_bytes_match": lowered_elems == layout.total_elems,
        "padding_fraction": layout.padding_fraction,
        "segment_waste": [s.waste for s in layout.segments],
        "n_buckets": cplan.n_buckets,
        "n_spans": layout.n_spans,
        "hlo_allreduce_arena": n_ar_arena,
        "hlo_allreduce_bucket": n_ar_bucket,
        "predicted_wire_bytes": predicted,
        "hlo_wire_bytes": measured,
        "padding_wire_bytes": padding_wire,
        "roofline": roof.as_dict(n_dev),
        "arena": layout.describe() | {"segments": None, "spans": None},
        "comm_plan": cplan.describe() | {"arena": None, "channels": None},
    }


def _count_pallas_calls(jaxpr, name_substr: str) -> int:
    """Recursively count ``pallas_call`` equations whose kernel name
    contains ``name_substr`` (sub-jaxprs in eqn params included)."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = str(eqn.params.get("name_and_src_info",
                                      eqn.params.get("name", "")))
            if name_substr in name:
                n += 1
        for v in eqn.params.values():
            for u in (v if isinstance(v, (list, tuple)) else (v,)):
                if hasattr(u, "jaxpr"):
                    n += _count_pallas_calls(u.jaxpr, name_substr)
    return n


def run_mem_codec_cell(arch: str, page_bytes: int, bucket_mb: float, *,
                       channels: int = 2, dp_mode: str = "replicated",
                       wire_codec: str = "int8") -> dict:
    """One quantized-wire mem cell: lower the ``dp_mode``'s gradient wire
    path twice — fp32 and under ``wire_codec`` — over the arch's (reduced)
    tree on an explicit ``ring`` transport, and hold the compressed
    prediction to the optimized HLO with zero tolerance:

    * **wire bytes** — parsed ``collective-permute`` operand bytes (int8
      payload + fp32 block scales both ride the ppermutes) must equal
      ``CommPlan.arena_bytes_per_device`` exactly, for the fp32 twin and
      the codec run alike;
    * **compression** — the codec cell must move ≥ 3.5× fewer
      predicted-and-lowered bytes than its fp32 twin (the acceptance
      ratio; ``1 + 4/block`` bytes/elem plus page padding);
    * **kernels** — on a channel-free pack of the same tree, the fused
      pack+quantize must lower to exactly one ``pallas_call`` per span
      (one fused encode per contiguous segment, no per-block dispatch).

    The three DP modes lower their own wire paths — ``replicated``
    all-reduces spans, ``zero1`` reduce-scatters spans then all-gathers
    the shards, ``fsdp`` lowers the reduce-scatter its weight-gather
    transpose executes (half an all-reduce) — over the *same* span layout,
    so the measured ratios must agree exactly across modes.
    """
    from jax.sharding import AxisType, PartitionSpec as P
    from repro.comm import CommConfig
    from repro.configs import reduced_config
    from repro.runtime.train_step import _local_shapes, build_comm

    mesh = jax.make_mesh((4, 1), ("data", "model"),
                         devices=jax.devices()[:4],
                         axis_types=(AxisType.Auto,) * 2)
    n_dev = 4
    model = build_model(reduced_config(arch))
    op = "all_reduce" if dp_mode == "replicated" else "reduce_scatter"
    gather_back = dp_mode == "zero1"      # fsdp keeps the shards

    def build(codec):
        tcfg = TrainStepConfig(
            dp_mode="replicated",      # the comm config is mode-agnostic
            comm=CommConfig(transport="ring", channels=channels,
                            bucket_bytes=int(bucket_mb * 2**20),
                            page_bytes=int(page_bytes), wire_codec=codec),
            schedule="scheduled", use_arena=True)
        return build_comm(mesh, tcfg)

    def lower(comm):
        pspecs = model.param_specs(mesh)
        local = _local_shapes(model.abstract_params(), pspecs, mesh)
        cplan = comm.plan(local)
        layout = cplan.arena_layout
        arena = comm.arena(local)
        sched = comm.arena_schedule(local, "scheduled", 1)
        quant = comm.codec is not None
        grads_abs = model.abstract_params()
        batch_abs = {"x": jax.ShapeDtypeStruct((1,), jnp.float32)}
        flat = P(tuple(mesh.axis_names))

        def grad_like(p, mb):
            return jnp.zeros((), jnp.float32), p

        def fn(buf, ef, grads, batch):
            kw = dict(arena=arena, arena_buf=buf)
            if quant:
                kw["ef_buf"] = ef
            _, out = comm.reduce_scheduled(grad_like, grads, batch, sched,
                                           op=op, **kw)
            if op == "all_reduce":
                tree, buf = out[0], out[1]
                ef = out[2] if quant else ef
                return buf, ef, tree
            shards, _, buf = out[0], out[1], out[2]
            ef = out[3] if quant else ef
            if gather_back:
                shards = comm.all_gather(shards)
            return buf, ef, shards

        n_out = layout.n_spans if op != "all_reduce" else None
        out_specs = (flat, flat,
                     pspecs if op == "all_reduce" else [flat] * n_out)
        f = jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=(flat, flat, pspecs, P()),
            out_specs=out_specs, check_vma=False), donate_argnums=(0, 1))
        arena_abs = jax.ShapeDtypeStruct((n_dev * layout.total_elems,),
                                         jnp.dtype(layout.dtype))
        ef_abs = jax.ShapeDtypeStruct(
            (n_dev * getattr(layout, "payload_elems", 1),), jnp.float32)
        compiled = f.lower(arena_abs, ef_abs, grads_abs, batch_abs).compile()
        stats = collective_wire_bytes(compiled.as_text())
        measured = sum(stats.op_bytes.values())
        predicted = cplan.arena_bytes_per_device
        if dp_mode == "fsdp":
            predicted = predicted / 2.0   # RS is half the AR ring volume
        if predicted and abs(measured - predicted) / predicted > 1e-9:
            raise AssertionError(
                f"{dp_mode}/{comm.codec or 'fp32'} wire bytes: predicted "
                f"{predicted}, HLO {measured}")
        return cplan, layout, predicted, measured

    t0 = time.time()
    with mesh:
        comm_f32, comm_q = build(None), build(wire_codec)
        _, _, pred_f32, meas_f32 = lower(comm_f32)
        cplan_q, layout_q, pred_q, meas_q = lower(comm_q)

        # fused pack+quantize: one kernel per span on a channel-free pack
        tcfg_k = TrainStepConfig(
            dp_mode="replicated",
            comm=CommConfig(transport="ring", channels=0,
                            bucket_bytes=int(bucket_mb * 2**20),
                            page_bytes=int(page_bytes),
                            wire_codec=wire_codec, local_op="pallas"),
            schedule="scheduled", use_arena=True)
        comm_k = build_comm(mesh, tcfg_k)
        pspecs = model.param_specs(mesh)
        local = _local_shapes(model.abstract_params(), pspecs, mesh)
        arena_k = comm_k.arena(local)
        lay_k = arena_k.layout
        bufs = [jax.ShapeDtypeStruct((lay_k.segment_of(b).size,),
                                     jnp.float32)
                for b in range(lay_k.n_segments)]
        jx = jax.make_jaxpr(
            lambda buf, ef, *bs: arena_k.pack_into(buf, list(bs), ef))(
            arena_k.abstract(), arena_k.ef_abstract(), *bufs)
        n_kernels = _count_pallas_calls(jx.jaxpr, "_pack_quant_kernel")
        if n_kernels != lay_k.n_spans:
            raise AssertionError(
                f"fused pack+quantize lowered to {n_kernels} pallas calls, "
                f"expected one per span ({lay_k.n_spans})")
    compile_s = time.time() - t0

    ratio = meas_f32 / meas_q if meas_q else 0.0
    if ratio < 3.5:
        raise AssertionError(
            f"codec wire-byte ratio {ratio:.3f} < 3.5 "
            f"(fp32 {meas_f32} B vs {wire_codec} {meas_q} B; page padding "
            f"too large? use small pages for codec cells)")
    return {
        "arch": arch, "suite": "mem", "cell": "codec",
        "dp_mode": dp_mode,
        "wire_codec": wire_codec,
        "codec_block": cplan_q.codec_block,
        "page_bytes": int(page_bytes),
        "bucket_mb": bucket_mb,
        "channels": channels,
        "transport": "ring",
        "mesh": "4x1",
        "devices": n_dev,
        "compile_s": compile_s,
        "predicted_wire_bytes_fp32": pred_f32,
        "hlo_wire_bytes_fp32": meas_f32,
        "predicted_wire_bytes_codec": pred_q,
        "hlo_wire_bytes_codec": meas_q,
        "wire_ratio": ratio,
        "bytes_match_fp32": abs(meas_f32 - pred_f32) <= 1e-9 * pred_f32,
        "bytes_match_codec": abs(meas_q - pred_q) <= 1e-9 * pred_q,
        "pack_quant_kernels": n_kernels,
        "n_spans_packed": lay_k.n_spans,
        "codec_tradeoff": cplan_q.codec_tradeoff(),
        "arena": layout_q.describe() | {"segments": None, "spans": None},
    }


def run_mem_suite(args, cache: dict, tuned_db=None) -> None:
    """The ``--suite mem`` grid: page_bytes × bucket_mb × arch, each cell
    asserting predicted arena bytes/pages/collective-counts against the
    lowered HLO with zero tolerance.  With ``--wire-codec`` the grid runs
    the quantized-wire codec cells instead — per DP mode, each asserting
    compressed-prediction == lowered bytes at 0 tolerance, a ≥ 3.5×
    fp32/codec wire ratio, and one fused pack+quantize kernel per span —
    then asserts the measured ratio is identical across the three modes."""
    archs = (MEM_DEFAULT_ARCHS if args.arch == "all"
             else args.arch.split(","))
    pages = [int(s) for s in str(args.page_bytes).split(",")]
    buckets = [float(s) for s in str(args.bucket_mb).split(",")]
    if args.wire_codec:
        run_mem_codec_grid(args, cache, archs, pages, buckets)
        return
    for arch in archs:
        for pb in pages:
            for bmb in buckets:
                grid = {"page_bytes": pb, "bucket_mb": bmb,
                        "channels": args.channels}
                if tuned_db is not None:
                    # tuned pricing is part of the cell identity: an
                    # untuned cached cell must not shadow a --tuned run
                    grid["tuned"] = os.path.basename(args.tuned)
                key = cell_key(args.tag, arch, "mem", f"p{pb}", grid)
                if key in cache and not args.force:
                    print(f"[cached] {key}")
                    continue
                print(f"[lower+compile] {key} ...", flush=True)
                t0 = time.time()
                try:
                    rec = run_mem_cell(arch, pb, bmb,
                                       channels=args.channels,
                                       tuned_db=tuned_db)
                    rec["tag"] = args.tag
                    cache[key] = rec
                    print(f"  ok in {time.time()-t0:.1f}s: "
                          f"arena={rec['predicted_arena_bytes']}B "
                          f"pages={rec['predicted_arena_pages']} "
                          f"pad={rec['padding_fraction']:.2%} "
                          f"collectives {rec['hlo_allreduce_arena']}"
                          f"(fused)/{rec['hlo_allreduce_bucket']}(bucket)",
                          flush=True)
                except Exception as e:
                    cache[key] = {"error": str(e), "tag": args.tag,
                                  "arch": arch, "shape": "mem"}
                    print(f"  FAILED: {e}")
                    traceback.print_exc()
                with open(args.out, "w") as f:
                    json.dump(cache, f, indent=1)


def run_mem_codec_grid(args, cache: dict, archs, pages, buckets) -> None:
    """The ``--wire-codec`` arm of the mem suite: one codec cell per
    (arch × page × bucket × DP mode), plus the cross-mode ratio assert."""
    for arch in archs:
        for pb in pages:
            for bmb in buckets:
                ratios = {}
                for dp_mode in ("replicated", "zero1", "fsdp"):
                    grid = {"page_bytes": pb, "bucket_mb": bmb,
                            "channels": args.channels,
                            "wire_codec": args.wire_codec,
                            "dp_mode": dp_mode}
                    key = cell_key(args.tag, arch, "mem-codec",
                                   f"p{pb}-{dp_mode}", grid)
                    if key in cache and not args.force:
                        print(f"[cached] {key}")
                        if "wire_ratio" in cache[key]:
                            ratios[dp_mode] = cache[key]["wire_ratio"]
                        continue
                    print(f"[lower+compile] {key} ...", flush=True)
                    t0 = time.time()
                    try:
                        rec = run_mem_codec_cell(
                            arch, pb, bmb, channels=args.channels,
                            dp_mode=dp_mode, wire_codec=args.wire_codec)
                        rec["tag"] = args.tag
                        cache[key] = rec
                        ratios[dp_mode] = rec["wire_ratio"]
                        print(f"  ok in {time.time()-t0:.1f}s: "
                              f"wire {rec['hlo_wire_bytes_fp32']:.0f}B -> "
                              f"{rec['hlo_wire_bytes_codec']:.0f}B "
                              f"(x{rec['wire_ratio']:.2f}), "
                              f"{rec['pack_quant_kernels']} fused "
                              f"pack+quantize kernels", flush=True)
                    except Exception as e:
                        cache[key] = {"error": str(e), "tag": args.tag,
                                      "arch": arch, "shape": "mem-codec"}
                        print(f"  FAILED: {e}")
                        traceback.print_exc()
                    with open(args.out, "w") as f:
                        json.dump(cache, f, indent=1)
                if len(ratios) == 3 and len(set(ratios.values())) != 1:
                    raise AssertionError(
                        f"codec wire ratio differs across DP modes: "
                        f"{ratios}")


SERVE_DEFAULT_ARCHS = ["llama3.2-1b", "qwen2-7b"]


def run_serve_cell(arch: str, page_tokens: int, model_parallel: int, *,
                   page_bytes: int = 4096, max_seqs: int = 4,
                   max_seq_len: int = 64) -> dict:
    """One ``--suite serve`` cell: lower + compile one paged decode step
    (``repro.serve``) on a ``(1, R)`` mesh and hold the serving prediction
    layer to the optimized HLO with zero tolerance:

    * **bytes/pages** — the donated page arena is the step's first argument
      → ENTRY parameter 0 of the compiled module; its lowered size must be
      exactly ``KVArenaPlan.total_elems`` elements of the cache dtype, i.e.
      predicted KV bytes == lowered buffer bytes and predicted page count
      == lowered bytes / page_bytes;
    * **counts** — one decode token must lower to exactly
      ``predicted_collectives_per_token(plan)`` all-reduce ops (the per-layer
      pmax + fused LSE stats reduce; zero when R == 1);
    * **wire bytes** — parsed all-reduce bytes must equal
      ``predicted_wire_bytes_per_token`` exactly (ring ``2(R-1)/R`` hops
      over the fp32 stats, nothing else crosses the wire per token).

    The roofline prices the per-token exposed comm with the α·messages
    latency term — decode is the α-bound regime, same as the paper's
    strong-scaled CG.
    """
    from jax.sharding import AxisType
    from repro.configs import reduced_config
    from repro.serve.engine import (build_paged_decode_step,
                                    predicted_collectives_per_token,
                                    predicted_wire_bytes_per_token)
    from repro.serve.kv import plan_kv_arena

    r = int(model_parallel)
    mesh = jax.make_mesh((1, r), ("data", "model"),
                         devices=jax.devices()[:r],
                         axis_types=(AxisType.Auto,) * 2)
    model = build_model(reduced_config(arch))
    plan = plan_kv_arena(model.cfg, mesh, page_tokens=page_tokens,
                         page_bytes=page_bytes, max_seqs=max_seqs,
                         max_seq_len=max_seq_len)
    b = plan.max_seqs
    with mesh:
        step, _, _ = build_paged_decode_step(model, mesh, plan,
                                             attn_impl="ref")
        pages_abs = jax.ShapeDtypeStruct((plan.total_elems,),
                                         plan.layout.dtype)
        table_abs = jax.ShapeDtypeStruct(
            (b, plan.max_blocks, plan.n_layers), jnp.int32)
        vec = jax.ShapeDtypeStruct((b,), jnp.int32)
        valid_abs = jax.ShapeDtypeStruct((b,), jnp.bool_)
        t0 = time.time()
        compiled = step.lower(pages_abs, model.abstract_params(), table_abs,
                              vec, vec, valid_abs).compile()
        compile_s = time.time() - t0

    txt = compiled.as_text()
    stats = collective_wire_bytes(txt)

    # --- the zero-tolerance prediction checks -----------------------------
    hlo_dtype = {"bfloat16": "bf16", "float32": "f32",
                 "float16": "f16"}[jnp.dtype(plan.layout.dtype).name]
    lowered_elems = _entry_param_elems(txt, 0, hlo_dtype)
    if lowered_elems != plan.total_elems:
        raise AssertionError(
            f"lowered page arena is {hlo_dtype}[{lowered_elems}], predicted "
            f"{hlo_dtype}[{plan.total_elems}] ({plan.total_bytes} B, "
            f"{plan.n_arena_pages} pages)")
    n_ar = stats.op_counts.get("all-reduce", 0)
    pred_count = predicted_collectives_per_token(plan)
    if n_ar != pred_count:
        raise AssertionError(
            f"decode step lowered to {n_ar} all-reduce ops per token, "
            f"predicted {pred_count} (2 per layer at R={r})")
    measured = stats.op_bytes.get("all-reduce", 0.0)
    predicted = predicted_wire_bytes_per_token(plan, model.cfg, b)
    if measured != predicted:
        raise AssertionError(
            f"per-token all-reduce wire bytes: predicted {predicted}, "
            f"HLO {measured}")

    ca = compiled.cost_analysis()
    roof = Roofline(
        flops_per_device=float(ca.get("flops", 0.0)),
        hbm_bytes_per_device=float(ca.get("bytes accessed", 0.0)),
        wire_bytes_per_device=predicted,
        messages_per_device=float(stats.messages),
        overlap_fraction=0.0,       # decode comm is on the critical path
    )
    return {
        "arch": arch, "suite": "serve",
        "page_tokens": page_tokens,
        "page_bytes": int(page_bytes),
        "mesh": f"1x{r}",
        "devices": r,
        "batch_slots": b,
        "max_seq_len": max_seq_len,
        "compile_s": compile_s,
        "predicted_kv_bytes": plan.total_bytes,
        "predicted_kv_pages": plan.n_arena_pages,
        "lowered_arena_elems": lowered_elems,
        "kv_bytes_match": lowered_elems == plan.total_elems,
        "padding_fraction": plan.padding_fraction,
        "predicted_collectives_per_token": pred_count,
        "hlo_allreduce_per_token": n_ar,
        "predicted_wire_bytes_per_token": predicted,
        "hlo_wire_bytes_per_token": measured,
        "hlo_messages": stats.messages,
        "roofline": roof.as_dict(r),
        "kv_plan": plan.describe(),
    }


def run_serve_suite(args, cache: dict) -> None:
    """The ``--suite serve`` grid: arch × page_tokens × model-parallel,
    each cell asserting predicted KV-arena bytes/pages and per-decode-token
    collective counts against the lowered HLO with zero tolerance."""
    archs = (SERVE_DEFAULT_ARCHS if args.arch == "all"
             else args.arch.split(","))
    pts = [int(s) for s in str(args.page_tokens).split(",")]
    rs = [int(s) for s in str(args.serve_mp).split(",")]
    for arch in archs:
        for pt in pts:
            for r in rs:
                grid = {"page_tokens": pt, "model_parallel": r}
                key = cell_key(args.tag, arch, "serve", f"r{r}", grid)
                if key in cache and not args.force:
                    print(f"[cached] {key}")
                    continue
                print(f"[lower+compile] {key} ...", flush=True)
                t0 = time.time()
                try:
                    rec = run_serve_cell(arch, pt, r)
                    rec["tag"] = args.tag
                    cache[key] = rec
                    print(f"  ok in {time.time()-t0:.1f}s: "
                          f"kv={rec['predicted_kv_bytes']}B "
                          f"pages={rec['predicted_kv_pages']} "
                          f"pad={rec['padding_fraction']:.2%} "
                          f"collectives/token={rec['hlo_allreduce_per_token']}"
                          f" wire/token={rec['hlo_wire_bytes_per_token']:.0f}B",
                          flush=True)
                except Exception as e:
                    cache[key] = {"error": str(e), "tag": args.tag,
                                  "arch": arch, "shape": "serve"}
                    print(f"  FAILED: {e}")
                    traceback.print_exc()
                with open(args.out, "w") as f:
                    json.dump(cache, f, indent=1)


MOE_DEFAULT_ARCHS = ["mixtral-8x7b", "llama4-maverick-400b-a17b"]


def run_moe_cell(arch: str, transport: str, channels: int,
                 model_parallel: int, parallelism: str, *,
                 batch: int = 8, seq: int = 32) -> dict:
    """One ``--suite moe`` cell: lower + compile one MoE forward loss on a
    ``(1, R)`` mesh and hold the :class:`~repro.comm.plan.A2APlan` to the
    optimized HLO:

    * **counts** — with ``parallelism='ep'`` every MoE layer must lower to
      exactly one dispatch + one combine exchange per rail in the
      transport's op family (``a2a`` → HLO ``all-to-all``, rings →
      ``collective-permute`` hops, ``psum`` → zero-padded ``all-reduce``);
      with ``parallelism='tp'`` the all-to-all count must be zero;
    * **wire bytes** — the parsed bytes of that op family must equal
      ``n_moe_layers * A2APlan.bytes_per_device`` at <1% tolerance (the
      parser and the plan price the same ring formulas, so the observed
      error is 0);
    * **dispatch tax** — the plan's per-device dispatch bytes must be at
      most ``1/R`` of the replicated-psum fallback's prediction for the
      same payload (the PR's headline acceptance bound).
    """
    from jax.sharding import AxisType, PartitionSpec as P
    from repro.comm.registry import get_transport
    from repro.configs import reduced_config
    from repro.models.moe import capacity
    from repro.runtime.train_step import build_moe_comm, make_ctx

    r = int(model_parallel)
    rcfg = reduced_config(arch)
    if rcfg.moe is None:
        raise ValueError(f"{arch} has no MoE block")
    rcfg = rcfg.with_(moe=replace(rcfg.moe, parallelism=parallelism))
    model = build_model(rcfg)
    cfg = model.cfg
    mesh = jax.make_mesh((1, r), ("data", "model"),
                         devices=jax.devices()[:r],
                         axis_types=(AxisType.Auto,) * 2)
    tcfg = TrainStepConfig(moe_transport=transport, moe_channels=channels)
    ctx = make_ctx(mesh, tcfg)
    comm = build_moe_comm(mesh, tcfg)

    n_moe = sum(1 for i in range(cfg.num_layers)
                if cfg.layer_kind(i)["mlp"] == "moe")
    e, d = cfg.moe.num_experts, cfg.d_model
    cap = capacity(seq, cfg.moe)
    bs = batch // r
    buf_shape = (bs, e, cap, d)          # the local EP dispatch payload
    plan = comm.a2a_plan(buf_shape, dtype=jnp.float32)
    sched = comm.moe_schedule(buf_shape, dtype=jnp.float32)
    sched.validate()

    # the acceptance bound: EP dispatch <= 1/R of the replicated-psum cost
    n_elems = plan.elems_per_device
    _, psum_cls = get_transport("psum")
    psum_t = psum_cls(("model",), None)
    replicated = psum_t.predicted_a2a_bytes_per_device(n_elems, r,
                                                       itemsize=4)
    if transport != "psum" and r > 1 and \
            plan.dispatch_bytes_per_device > replicated / r:
        raise AssertionError(
            f"EP dispatch bytes {plan.dispatch_bytes_per_device:.0f} exceed "
            f"1/R of the replicated-psum cost {replicated:.0f} at R={r}")

    pspecs = model.param_specs(mesh)
    batch_abs = {"tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((batch, seq), jnp.int32)}
    bspecs = {"tokens": P(), "labels": P()}

    def lower_with(ctx_):
        def fwd(p, mb):
            return model.loss_fn(p, mb, ctx=ctx_)

        sh = jax.shard_map(fwd, mesh=mesh, in_specs=(pspecs, bspecs),
                           out_specs=P(), check_vma=False)
        with mesh:
            return jax.jit(sh).lower(model.abstract_params(),
                                     batch_abs).compile()

    t0 = time.time()
    compiled = lower_with(ctx)
    compile_s = time.time() - t0
    txt = compiled.as_text()
    stats = collective_wire_bytes(txt)

    # which HLO op family carries the exchange, and the expected op count
    rails = comm.a2a_rails(buf_shape)
    ep_active = parallelism == "ep" and r > 1 and e % r == 0 \
        and batch % r == 0
    family = {"a2a": "all-to-all", "ring": "collective-permute",
              "ring_hier": "collective-permute", "psum": "all-reduce"}[
                  transport]
    if not ep_active:
        want_ops = 0
        predicted_bytes = 0.0
    elif family == "all-to-all":
        want_ops = n_moe * 2 * rails
        predicted_bytes = n_moe * plan.bytes_per_device
    elif family == "collective-permute":
        want_ops = n_moe * 2 * rails * (r - 1)
        predicted_bytes = n_moe * plan.bytes_per_device
    else:                                 # psum fallback
        want_ops = n_moe * 2 * rails
        predicted_bytes = n_moe * plan.bytes_per_device

    n_ops = stats.op_counts.get(family, 0)
    measured = stats.op_bytes.get(family, 0.0)
    if family == "all-reduce" and ep_active:
        # the psum fallback shares its op family with the model's TP
        # all-reduces; diff against the identical graph lowered with the
        # native-a2a transport to isolate the exchange's contribution
        bg = collective_wire_bytes(lower_with(make_ctx(
            mesh, replace(tcfg, moe_transport="a2a"))).as_text())
        n_ops -= bg.op_counts.get(family, 0)
        measured -= bg.op_bytes.get(family, 0.0)
    if parallelism == "tp" and stats.op_counts.get("all-to-all", 0):
        raise AssertionError(
            f"tp parallelism lowered {stats.op_counts['all-to-all']} "
            f"all-to-all ops; expected none")
    if ep_active:
        if n_ops != want_ops:
            raise AssertionError(
                f"{family} op count {n_ops} != predicted {want_ops} "
                f"({n_moe} MoE layers x dispatch+combine x {rails} rails)")
        err = (abs(measured - predicted_bytes) / predicted_bytes
               if predicted_bytes else 0.0)
        if err >= 0.01:
            raise AssertionError(
                f"{family} wire bytes: predicted {predicted_bytes:.0f}, "
                f"HLO {measured:.0f} (err {err:.2%} >= 1%)")
    else:
        err = 0.0

    ca = compiled.cost_analysis()
    roof = Roofline(
        flops_per_device=float(ca.get("flops", 0.0)),
        hbm_bytes_per_device=float(ca.get("bytes accessed", 0.0)),
        wire_bytes_per_device=stats.wire_bytes,
        messages_per_device=float(stats.messages),
        overlap_fraction=sched.overlap_fraction if ep_active else 0.0,
    )
    return {
        "arch": arch, "suite": "moe",
        "transport": transport, "channels": channels, "rails": rails,
        "parallelism": parallelism, "mesh": f"1x{r}", "devices": r,
        "ep_active": ep_active,
        "n_moe_layers": n_moe, "capacity": cap,
        "buf_shape": list(buf_shape),
        "compile_s": compile_s,
        "predicted_a2a_bytes": predicted_bytes,
        "hlo_a2a_bytes": measured,
        "byte_err": err,
        "predicted_a2a_ops": want_ops,
        "hlo_a2a_ops": n_ops,
        "dispatch_bytes_per_device": plan.dispatch_bytes_per_device,
        "replicated_psum_bytes": replicated,
        "dispatch_vs_replicated":
            (plan.dispatch_bytes_per_device / replicated if replicated
             else 0.0),
        "messages_per_device": plan.messages_per_device,
        "overlap_fraction": sched.overlap_fraction,
        "a2a_plan": plan.describe(),
        "roofline": roof.as_dict(r),
    }


def run_moe_suite(args, cache: dict) -> None:
    """The ``--suite moe`` grid: arch × transport × channels × parallelism,
    each cell asserting predicted all-to-all ops/bytes against the lowered
    HLO (<1% tolerance) and the EP-dispatch-tax bound vs the replicated
    psum fallback."""
    archs = (MOE_DEFAULT_ARCHS if args.arch == "all"
             else args.arch.split(","))
    transports = str(args.moe_transports).split(",")
    chans = [int(s) for s in str(args.moe_channels).split(",")]
    rs = [int(s) for s in str(args.moe_mp).split(",")]
    for arch in archs:
        for transport in transports:
            for ch in chans:
                for r in rs:
                    for par in ("ep", "tp"):
                        if par == "tp" and (transport != "a2a" or ch != 0):
                            continue   # tp lowers no exchange; one cell enough
                        grid = {"transport": transport, "channels": ch,
                                "parallelism": par}
                        key = cell_key(args.tag, arch, "moe", f"r{r}", grid)
                        if key in cache and not args.force:
                            print(f"[cached] {key}")
                            continue
                        print(f"[lower+compile] {key} ...", flush=True)
                        t0 = time.time()
                        try:
                            rec = run_moe_cell(arch, transport, ch, r, par)
                            rec["tag"] = args.tag
                            cache[key] = rec
                            print(
                                f"  ok in {time.time()-t0:.1f}s: "
                                f"ops={rec['hlo_a2a_ops']} "
                                f"bytes={rec['hlo_a2a_bytes']:.0f} "
                                f"(err {rec['byte_err']:.2%}) "
                                f"dispatch/replicated="
                                f"{rec['dispatch_vs_replicated']:.3f}",
                                flush=True)
                        except Exception as e:
                            cache[key] = {"error": str(e), "tag": args.tag,
                                          "arch": arch, "shape": "moe"}
                            print(f"  FAILED: {e}")
                            traceback.print_exc()
                        with open(args.out, "w") as f:
                            json.dump(cache, f, indent=1)


STENCIL_MESH = {"single": ((4, 8, 8), 256), "multi": ((8, 8, 8), 512)}


def run_stencil_cell(L: int, schedule: str, multi_pod: bool, *,
                     channels: int = 2, halo: int = 1, components: int = 12,
                     cg_iters: int = 3, solver: str = "cg",
                     precond: str = "none", sstep_s: int = 4) -> dict:
    """One stencil-suite cell: lower + compile ``cg_iters`` unrolled
    iterations of one ``solver × precond`` variant on a Wilson-like operator
    over a 3-D Cartesian mesh, then check the prediction layer against the
    optimized HLO on *two* axes:

    * **bytes** — :class:`~repro.comm.HaloPlan` payloads vs the parsed
      ``collective-permute`` bytes (halo exchanges scale with the variant:
      even-odd hops twice per matvec plus projection/reconstruction);
    * **counts** — :func:`repro.stencil.predicted_reduction_collectives` /
      :func:`~repro.stencil.predicted_halo_exchanges` vs the parsed
      ``all-reduce`` / ``collective-permute`` op counts.  The count check is
      the latency-model (α·messages) analogue of the byte check: it is what
      distinguishes classic CG's ``2·iters+1`` reductions from pipelined's
      ``iters`` and s-step's ``ceil(iters/s)``.

    Inner products ride ``psum`` all-reduces, so the two op kinds separate
    cleanly in the parse."""
    from jax.sharding import AxisType, PartitionSpec as P
    from repro.comm import CommConfig, Communicator
    from repro.core.halo import HaloSpec
    from repro.stencil import (StencilOp, predicted_halo_exchanges,
                               predicted_reduction_collectives, solve)

    mesh_shape, n_dev = STENCIL_MESH["multi" if multi_pod else "single"]
    mesh = jax.make_mesh(mesh_shape, ("x", "y", "z"),
                         devices=jax.devices()[:n_dev],
                         axis_types=(AxisType.Auto,) * len(mesh_shape))
    specs = (HaloSpec("x", 0, halo), HaloSpec("y", 1, halo),
             HaloSpec("z", 2, halo))
    local = (L, L, L, components)
    gshape = tuple(p * n for p, n in zip(mesh_shape + (1,), local))
    comm = Communicator(mesh, CommConfig(transport="psum",
                                         data_axes=("x", "y", "z"),
                                         channels=channels))
    op = StencilOp(specs=specs, mass=0.8)
    hplan = comm.halo_plan(local, specs, schedule=schedule)
    hsched = comm.halo_schedule(local, specs, schedule=schedule)

    def run(b):
        r = solve(op, b, comm, solver=solver, precond=precond, s=sstep_s,
                  tol=None, maxiter=cg_iters, schedule=schedule,
                  chunks=comm.halo_chunks, channels=channels)
        return r.x, r.rel_residual

    with mesh:
        fn = jax.jit(jax.shard_map(
            run, mesh=mesh, in_specs=P("x", "y", "z", None),
            out_specs=(P("x", "y", "z", None), P()), check_vma=False))
        lowered = fn.lower(jax.ShapeDtypeStruct(gshape, jnp.float32))
    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0
    ca = compiled.cost_analysis()
    stats = collective_wire_bytes(compiled.as_text())
    n_exchanges = predicted_halo_exchanges(solver, precond, cg_iters,
                                           s=sstep_s)
    n_reductions = predicted_reduction_collectives(solver, cg_iters,
                                                   s=sstep_s)
    predicted = n_exchanges * hplan.bytes_per_device
    measured = stats.op_bytes.get("collective-permute", 0.0)
    pred_permutes = n_exchanges * hplan.n_units
    hlo_permutes = stats.op_counts.get("collective-permute", 0)
    hlo_reductions = stats.op_counts.get("all-reduce", 0)
    roof = Roofline(
        flops_per_device=float(ca.get("flops", 0.0)),
        hbm_bytes_per_device=float(ca.get("bytes accessed", 0.0)),
        wire_bytes_per_device=stats.wire_bytes,
        overlap_fraction=hsched.overlap_fraction,
        messages_per_device=stats.messages,
    )
    return {
        "arch": "stencil",
        "shape": f"L{L}h{halo}",
        "schedule": schedule,
        "solver": solver,
        "precond": precond,
        "sstep_s": sstep_s,
        "mesh": "x".join(str(s) for s in mesh_shape),
        "devices": n_dev,
        "compile_s": compile_s,
        "cg_iters": cg_iters,
        "predicted_halo_bytes": predicted,
        "hlo_collective_permute_bytes": measured,
        "halo_bytes_rel_err": (abs(measured - predicted) / predicted
                               if predicted else None),
        "predicted_halo_exchanges": n_exchanges,
        "predicted_permute_collectives": pred_permutes,
        "hlo_permute_collectives": hlo_permutes,
        "predicted_reduction_collectives": n_reductions,
        "hlo_reduction_collectives": hlo_reductions,
        "roofline": roof.as_dict(n_dev),
        "collectives": {"counts": stats.op_counts, "bytes": stats.op_bytes,
                        "while_loops": stats.while_loops},
        "halo_plan": hplan.describe(),
        "halo_schedule": hsched.describe(),
    }


def run_stencil_suite(args, meshes, cache: dict) -> None:
    """The ``--suite stencil`` grid: lattice × halo schedule × solver ×
    precond × mesh.  Cells land in the same cache/out file as the train
    suite, keyed through :func:`cell_key` so every grid knob is part of
    the cache identity."""
    from repro.comm import HALO_SCHEDULES
    from repro.stencil import PRECONDS, SOLVERS

    lattices = [int(s) for s in str(args.lattice).split(",")]
    schedules = (list(HALO_SCHEDULES) if args.halo_schedule == "all"
                 else args.halo_schedule.split(","))
    solvers = list(SOLVERS) if args.solver == "all" else args.solver.split(",")
    preconds = (list(PRECONDS) if args.precond == "all"
                else args.precond.split(","))
    for L in lattices:
        for schedule in schedules:
            for solver in solvers:
                for precond in preconds:
                    for multi in meshes:
                        grid = {"schedule": schedule, "solver": solver,
                                "precond": precond, "channels": args.channels,
                                "cg_iters": args.cg_iters,
                                "sstep_s": args.sstep_s}
                        key = cell_key(args.tag, "stencil",
                                       f"L{L}h{args.halo}",
                                       "multi" if multi else "single", grid)
                        if key in cache and not args.force:
                            print(f"[cached] {key}")
                            continue
                        print(f"[lower+compile] {key} ...", flush=True)
                        t0 = time.time()
                        try:
                            rec = run_stencil_cell(
                                L, schedule, multi, channels=args.channels,
                                halo=args.halo, cg_iters=args.cg_iters,
                                solver=solver, precond=precond,
                                sstep_s=args.sstep_s)
                            rec["tag"] = args.tag
                            cache[key] = rec
                            r = rec["roofline"]
                            err = rec["halo_bytes_rel_err"]
                            print(
                                f"  ok in {time.time()-t0:.1f}s: "
                                f"halo_bytes={rec['predicted_halo_bytes']:.0f}"
                                f" (HLO err {err:.2%}) reductions="
                                f"{rec['predicted_reduction_collectives']}"
                                f"/{rec['hlo_reduction_collectives']} "
                                f"permutes="
                                f"{rec['predicted_permute_collectives']}"
                                f"/{rec['hlo_permute_collectives']} "
                                f"Tx={r['t_collective_s']:.6f}s "
                                f"Tx_exposed="
                                f"{r['t_exposed_collective_s']:.6f}s "
                                f"overlap={r['overlap_fraction']:.2f}",
                                flush=True)
                        except Exception as e:
                            cache[key] = {"error": str(e), "tag": args.tag,
                                          "arch": "stencil", "shape": f"L{L}"}
                            print(f"  FAILED: {e}")
                            traceback.print_exc()
                        with open(args.out, "w") as f:
                            json.dump(cache, f, indent=1)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun.json")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--tuned", default=None, metavar="DB",
                    help="tuning DB (repro.tune.probe output): price each "
                         "train/mem cell's collective roofline term with "
                         "the *measured* α/bandwidth of the closest fitted "
                         "record and attach the fit's predicted-vs-measured "
                         "residuals as the cell's model_error field")
    ap.add_argument("--microbatches", type=int, default=1,
                    help="grad-accum slices for train cells; the dry-run "
                         "default of 1 keeps unrolled-HLO compile times "
                         "tractable on this 1-core container (roofline "
                         "FLOP/byte/wire terms are accumulation-invariant)")
    ap.add_argument("--accum-policy", default="accumulate_then_reduce",
                    choices=SCHEDULE_POLICIES,
                    help="issue schedule for the gradient reduction "
                         "(stream/scheduled overlap comm with backward "
                         "compute; reflected in t_exposed_collective)")
    ap.add_argument("--suite", default="train",
                    choices=["train", "stencil", "mem", "serve", "moe"],
                    help="train: the arch x shape grid below; stencil: the "
                         "QCD workload — lattice-volume x halo-schedule "
                         "cells on a 3-D Cartesian mesh, checking HaloPlan "
                         "predictions against lowered collective-permutes; "
                         "mem: the repro.mem arena grid — page_bytes x "
                         "bucket_mb x arch cells asserting predicted arena "
                         "bytes/pages/collective counts against lowered "
                         "HLO with zero tolerance; serve: the repro.serve "
                         "grid — arch x page_tokens x model-parallel paged "
                         "decode steps asserting predicted KV bytes/pages "
                         "and per-token collective counts against lowered "
                         "HLO with zero tolerance; moe: the expert-parallel "
                         "grid — arch x transport x channels x ep/tp MoE "
                         "forward losses asserting predicted all-to-all "
                         "ops/bytes (A2APlan) against lowered HLO at <1%% "
                         "tolerance and the EP dispatch <= replicated/R "
                         "bound")
    ap.add_argument("--page-bytes", default="4096,2097152",
                    help="mem suite: comma-separated arena page sizes "
                         "(default: 4 KiB small-page baseline and the "
                         "paper's 2 MiB huge page)")
    ap.add_argument("--bucket-mb", default="1",
                    help="mem suite: comma-separated bucketer targets in "
                         "MiB")
    ap.add_argument("--wire-codec", default=None, choices=["int8"],
                    help="mem suite: run the quantized-wire codec cells "
                         "instead — per DP mode, asserting compressed "
                         "prediction == lowered collective bytes at zero "
                         "tolerance, a >=3.5x fp32/codec wire ratio, and "
                         "one fused pack+quantize kernel per span (use "
                         "small --page-bytes, e.g. 4096: 2 MiB pages "
                         "quantize the int8 payload 4x coarser and the "
                         "padding eats the ratio)")
    ap.add_argument("--moe-transports", default="a2a,psum",
                    help="moe suite: comma-separated exchange transports "
                         "(a2a,ring,ring_hier,psum)")
    ap.add_argument("--moe-channels", default="0,2",
                    help="moe suite: comma-separated rail counts for the "
                         "EP payload's feature-dim striping (0 = single)")
    ap.add_argument("--moe-mp", default="2",
                    help="moe suite: comma-separated model-axis sizes R")
    ap.add_argument("--page-tokens", default="8,16",
                    help="serve suite: comma-separated KV page sizes in "
                         "token positions")
    ap.add_argument("--serve-mp", default="1,2",
                    help="serve suite: comma-separated model-axis sizes R "
                         "to lower the paged decode step on (host devices "
                         "are forced, so any R works without hardware)")
    ap.add_argument("--lattice", default="8",
                    help="stencil suite: comma-separated local lattice "
                         "extents (local volume = L^3 x 12 components)")
    ap.add_argument("--halo-schedule", default="all",
                    help="stencil suite: comma-separated halo schedules, or "
                         "'all'")
    ap.add_argument("--halo", type=int, default=1,
                    help="stencil suite: face width (1 or 2)")
    ap.add_argument("--channels", type=int, default=2,
                    help="stencil suite: communicator virtual channels")
    ap.add_argument("--cg-iters", type=int, default=3,
                    help="stencil suite: unrolled CG iterations per cell")
    ap.add_argument("--solver", default="cg",
                    help="stencil suite: comma-separated solver variants "
                         "(cg,pipelined,sstep) or 'all' — the predicted "
                         "reduction-collective count drops from 2·iters+1 "
                         "to iters to ceil(iters/s) along that list")
    ap.add_argument("--precond", default="none",
                    help="stencil suite: comma-separated preconditioners "
                         "(none,eo) or 'all'")
    ap.add_argument("--sstep-s", type=int, default=4,
                    help="stencil suite: s-step block size (reductions per "
                         "solve = ceil(cg_iters/s))")
    args = ap.parse_args()

    archs = list_archs() if args.arch == "all" else args.arch.split(",")
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    tuned_db = None
    if args.tuned:
        from repro.tune.db import TuningDB

        tuned_db = TuningDB.load(args.tuned)
        print(f"[tuned] {args.tuned}: {len(tuned_db)} fitted record(s)")

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    cache: dict = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            cache = json.load(f)

    if args.suite in ("stencil", "mem", "serve", "moe"):
        if args.suite == "stencil":
            run_stencil_suite(args, meshes, cache)
        elif args.suite == "mem":
            run_mem_suite(args, cache, tuned_db=tuned_db)
        elif args.suite == "moe":
            run_moe_suite(args, cache)
        else:
            run_serve_suite(args, cache)
        n_ok = sum(1 for v in cache.values() if "error" not in v)
        n_err = sum(1 for v in cache.values() if "error" in v)
        print(f"done: {n_ok} ok, {n_err} failed -> {args.out}")
        return

    for arch in archs:
        cfg = get_config(arch)
        shapes = (applicable_shapes(cfg) if args.shape == "all"
                  else args.shape.split(","))
        for shape_name in shapes:
            if shape_name not in applicable_shapes(cfg):
                print(f"[skip] {arch} x {shape_name}: inapplicable "
                      f"(sub-quadratic rule, see DESIGN.md)")
                continue
            for multi in meshes:
                overrides = {"accum_microbatches": args.microbatches,
                             "accum_policy": args.accum_policy}
                key_over = dict(overrides)
                if tuned_db is not None:
                    # tuned pricing is part of the cell identity (key only:
                    # make_step_config must not see the marker)
                    key_over["tuned"] = os.path.basename(args.tuned)
                key = cell_key(args.tag, arch, shape_name,
                               "multi" if multi else "single", key_over)
                if key in cache and not args.force:
                    print(f"[cached] {key}")
                    continue
                print(f"[lower+compile] {key} ...", flush=True)
                t0 = time.time()
                try:
                    rec = run_cell(arch, shape_name, multi,
                                   overrides=overrides, tuned_db=tuned_db)
                    rec["tag"] = args.tag
                    cache[key] = rec
                    r = rec["roofline"]
                    print(f"  ok in {time.time()-t0:.1f}s: "
                          f"bottleneck={r['bottleneck']} "
                          f"Tc={r['t_compute_s']:.4f}s Tm={r['t_memory_s']:.4f}s "
                          f"Tx={r['t_collective_s']:.4f}s "
                          f"Tx_exposed={r['t_exposed_collective_s']:.4f}s "
                          f"overlap={r['overlap_fraction']:.2f} "
                          f"live={rec['memory']['live_gb']:.2f}GB "
                          f"fits={rec['memory']['fits_16gb']}", flush=True)
                except Exception as e:
                    cache[key] = {"error": str(e), "tag": args.tag,
                                  "arch": arch, "shape": shape_name}
                    print(f"  FAILED: {e}")
                    traceback.print_exc()
                with open(args.out, "w") as f:
                    json.dump(cache, f, indent=1)
    n_ok = sum(1 for v in cache.values() if "error" not in v)
    n_err = sum(1 for v in cache.values() if "error" in v)
    print(f"done: {n_ok} ok, {n_err} failed -> {args.out}")


if __name__ == "__main__":
    main()

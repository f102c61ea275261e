"""Smoke run of the system's main paths on TPU chips.

    python chip_smoke.py             # one chip: training + paged serving
    python chip_smoke.py --chips 4   # four chips: the cross-chip paths only

One chip, llama3.2-1b at its published widths (d_model 2048, 32/8 heads of
64, d_ff 8192, vocab 128256, tied embeddings), random weights from
``--seed``:

* ``train`` — ``Trainer`` over ``build_train_step`` with the arch's own
  ``settings_for`` comm config (ZeRO-1 over the ``ring_hier`` transport),
  a few steps at seq 2048.  Depth is the only cut: the most layers whose
  compiled step fits the chip's memory with 10% headroom
  (``compiled.memory_analysis()``).  Checks: finite losses and grad norms,
  step-0 loss within 0.5 of ln(vocab), and step-0 loss equal to
  ``model.loss_fn`` on the same parameters and batch outside the step.
* ``serve`` — all 16 layers through ``PagedDecodeEngine`` +
  ``ServeScheduler`` with the Pallas flash-decode kernel: 8 slots, 16
  requests (prompts up to 128 tokens, 32-64 output tokens).  Checks: the
  compiled decode step holds the kernel (``tpu_custom_call``), every
  request finishes, and the final logits of a few requests match a plain
  full-sequence ``model.forward`` of the same tokens.

Four chips (``--chips 4``):

* ``dp`` — data parallelism over 4 chips: the arch's ZeRO-1 over
  ``ring_hier`` against replicated parameters reduced by ``psum``, from the
  same initial state and batches, two steps.  The two transports on the
  step's bucket sizes must agree to fp32 rounding; step-0 losses must be
  equal; Adam's first moments (linear in the reduced gradients) must
  agree leaf by leaf to the precision of the bf16 local gradients; each
  run's updated parameters must be the AdamW update of its own moments.
* ``halo`` — ``solve(..., solver="cg")`` on a 2x2 decomposition of a
  lattice of 64^3 sites per chip against the single-device reference
  solve (``comm=None``) of the same problem.

Each phase prints one ``{"phase": ...}`` JSON line (what ran, the layers
kept, set-up and compile seconds, step or request times, the correctness
numbers); the last line is ``{"ok": ..., "device": {...}}``.  Without a
TPU the script exits non-zero and prints no result.  Times are smoke
output, not benchmark numbers.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import math
import os
import sys
import time

import numpy as np

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402
from jax.sharding import AxisType, NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.comm import CommConfig, Communicator  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.configs.base import ModelConfig, ShapeConfig  # noqa: E402
from repro.core.halo import HaloSpec  # noqa: E402
from repro.data import (DataConfig, SyntheticTokens,  # noqa: E402
                        make_batch_specs)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.settings import settings_for  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.optim import OptimConfig  # noqa: E402
from repro.runtime.train_loop import Trainer, TrainerConfig  # noqa: E402
from repro.runtime.train_step import (TrainStepConfig,  # noqa: E402
                                      build_comm, build_train_step,
                                      init_train_state)
from repro.serve.engine import PagedDecodeEngine  # noqa: E402
from repro.serve.kv import kv_page_payload_elems, plan_kv_arena  # noqa: E402
from repro.serve.scheduler import (Request, ServeScheduler,  # noqa: E402
                                   request_token)
from repro.stencil import StencilOp, solve  # noqa: E402

ARCH = "llama3.2-1b"
HEADROOM = 0.9            # share of device memory a compiled step may take
LOSS0_SLACK = 0.5         # |step-0 loss - ln(vocab)| at random init
BF16_EPS = 2.0 ** -7      # bf16 machine epsilon
LOSS_RTOL = BF16_EPS      # train: step-0 loss vs model.loss_fn outside it
# serve: |paged logits - forward logits| <= LOGIT_EPS bf16 eps of the
# largest logit.  The two paths round the bf16 residual stream at different
# points over 16 layers (2.2 eps measured at d_model 512 on the CPU).
LOGIT_EPS = 4
# dp: the arch transport's reduce-scatter + all-gather against psum's
# all-reduce on the same fp32 buckets: |difference| <= (world - 1) *
# eps32 * sum_i |x_i|, the rounding bound of two orders of one sum
FP32_EPS = 2.0 ** -23
# dp: per leaf, |mu_ring - mu_psum| over |mu_psum|, in the L2 norm and in
# the largest element.  The two train steps are different programs, so
# their bf16 local gradients are not bitwise equal (the reduction alone is
# held to fp32 rounding above): on a v5e 2x2 at 6 layers the moments were
# 2.9e-3 (L2) and 4.7e-3 (max) apart, 0.09% of the elements equal.  A
# misrouted bucket segment moves a leaf by more than 0.1
MOMENT_RTOL = 2 * BF16_EPS
# dp: |params after the update - AdamW of the run's own moments, computed
# outside the step|, in units of the lr, beyond 4 ulp of the parameter.
# The two differ by a few ulp of lr*update; a misrouted update is O(lr)
UPDATE_ATOL_LR = 1e-3
GNORM_RTOL = 1e-5         # dp: grad norms, fp32 sums in another order
HALO_RTOL = 1e-5          # halo: |x - x_ref| / |x_ref|
# dp depth: the replicated psum reference holds 16 B per parameter on
# every chip plus its all-reduce buffers.  memory_analysis on a described
# v5e:2x2 topology: 14.1 GB at 6 layers, 15.6 GB at 7, 16.7 GB at 9 (the
# one-chip train phase's cut) against a 15.2 GB budget — so 6 of 16
DP_LAYERS = 6


def _mesh(devices, shape, names):
    return jax.make_mesh(shape, names,
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def _peak_bytes(compiled) -> int:
    """Device bytes a compiled program holds at its peak (donated inputs
    counted once)."""
    ma = compiled.memory_analysis()
    return int(ma.argument_size_in_bytes + ma.output_size_in_bytes
               - ma.alias_size_in_bytes + ma.temp_size_in_bytes)


def _sharded_abstract(tree, specs, mesh):
    return jax.tree.map(
        lambda s, a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=NamedSharding(mesh, s)),
        specs, tree, is_leaf=lambda x: isinstance(x, P))


def step_peak_bytes(cfg: ModelConfig, mesh, step_cfg: TrainStepConfig,
                    seq: int, batch: int) -> int:
    """Compile the train step of ``cfg`` for ``mesh`` from shapes alone
    and return its peak device bytes."""
    model = build_model(cfg)
    shape = ShapeConfig("smoke", seq, batch, "train")
    with mesh:
        step = build_train_step(model, mesh, step_cfg,
                                make_batch_specs(cfg, shape, mesh))
        state_abs, sspecs = init_train_state(model, mesh, step_cfg,
                                             abstract=True)
        tok = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
        lowered = step.lower(_sharded_abstract(state_abs, sspecs, mesh),
                             {"tokens": tok, "labels": tok})
    return _peak_bytes(lowered.compile())


def fit_depth(peak_of, budget: int, max_layers: int) -> tuple[int, dict]:
    """Most layers ``n <= max_layers`` with ``peak_of(n) <= budget``.

    Peak bytes grow linearly with depth (the layers are unrolled, each a
    remat boundary), so two compiles give the slope; the predicted depth
    is then compiled to confirm, stepping down while it does not fit."""
    peaks = {n: peak_of(n) for n in (1, 2) if n <= max_layers}
    if peaks[1] > budget:
        raise RuntimeError(f"one layer needs {peaks[1]} B, budget {budget} B")
    if max_layers == 1:
        return 1, peaks
    per_layer = max(peaks[2] - peaks[1], 1)
    n = min(max_layers, 1 + (budget - peaks[1]) // per_layer)
    while n > 2:
        peaks[n] = peak_of(n)
        if peaks[n] <= budget:
            break
        n -= 1
    if n == 2 and peaks[2] > budget:
        n = 1
    return int(n), peaks


def _budget(device, override: int | None) -> int:
    if override is not None:
        return override
    stats = device.memory_stats() or {}
    if "bytes_limit" not in stats:
        raise RuntimeError(f"{device} reports no bytes_limit; pass a budget")
    return int(HEADROOM * stats["bytes_limit"])


def _smoke_optim(steps: int) -> OptimConfig:
    return OptimConfig(base_lr=3e-4, warmup=1, total_steps=max(steps, 2))


def _arch_step_cfg(steps: int) -> TrainStepConfig:
    st = settings_for(ARCH)
    return TrainStepConfig(dp_mode=st.dp_mode,
                           comm=st.comm_config(),
                           optim=_smoke_optim(steps),
                           microbatches=st.microbatches)


def _finite(xs) -> bool:
    return all(math.isfinite(x) for x in xs)


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------


def train_phase(devices, cfg: ModelConfig, *, seq: int = 2048,
                batch: int = 1, steps: int = 5, seed: int = 0,
                budget: int | None = None, log=print) -> dict:
    """Train ``cfg`` (depth cut to fit ``budget``) through the Trainer."""
    mesh = _mesh(devices[:1], (1, 1), ("data", "model"))
    step_cfg = _arch_step_cfg(steps)
    full_layers = cfg.num_layers
    budget = _budget(devices[0], budget)
    t0 = time.time()
    layers, peaks = fit_depth(
        lambda n: step_peak_bytes(cfg.with_(num_layers=n), mesh, step_cfg,
                                  seq, batch),
        budget, full_layers)
    fit_s = time.time() - t0
    cut = cfg.with_(num_layers=layers)
    model = build_model(cut)
    shape = ShapeConfig("smoke", seq, batch, "train")
    data = SyntheticTokens(DataConfig(vocab_size=model.cfg.vocab_size,
                                      seq_len=seq, global_batch=batch,
                                      seed=seed), model_cfg=cut)
    t0 = time.time()
    trainer = Trainer(model, mesh, step_cfg, data, shape,
                      TrainerConfig(steps=steps, log_every=1, seed=seed),
                      log=log)
    build_s = time.time() - t0
    params0 = jax.device_get(trainer.state["params"])
    hist = trainer.run()["history"]
    del trainer
    batch0 = data.batch_at(0)
    ref = float(jax.jit(model.loss_fn)(params0, batch0))
    losses = [h["loss"] for h in hist]
    gnorms = [h["grad_norm"] for h in hist]
    ln_v = math.log(cfg.vocab_size)
    checks = {
        "finite": _finite(losses) and _finite(gnorms),
        "loss0_near_ln_vocab": abs(losses[0] - ln_v) <= LOSS0_SLACK,
        "loss0_matches_loss_fn": abs(losses[0] - ref) <= LOSS_RTOL * abs(ref),
    }
    return {
        "phase": "train", "arch": cfg.name, "ok": all(checks.values()),
        "checks": checks, "layers_kept": layers, "layers_total": full_layers,
        "widths": _widths(cfg), "dp_mode": step_cfg.dp_mode,
        "transport": step_cfg.comm.transport, "seq": seq, "batch": batch,
        "peak_bytes_by_depth": peaks, "budget_bytes": budget,
        "setup_s": {"depth_fit_compiles": fit_s, "trainer_build": build_s,
                    "first_step_incl_compile": hist[0]["sec"]},
        "step_s": [h["sec"] for h in hist[1:]],
        "losses": losses, "grad_norms": gnorms, "ln_vocab": ln_v,
        "loss0_ref": ref, "loss0_abs_err": abs(losses[0] - ref),
    }


def _widths(cfg: ModelConfig) -> dict:
    a = cfg.attn
    return {"d_model": cfg.d_model, "heads": a.num_heads,
            "kv_heads": a.num_kv_heads, "head_dim": a.head_dim,
            "d_ff": cfg.d_ff, "vocab": cfg.vocab_size,
            "tied": cfg.tie_embeddings}


def serve_requests(n: int, *, prompt_max: int, out_min: int, out_max: int,
                   seed: int) -> list[Request]:
    rng = np.random.default_rng(seed)
    return [Request(rid, int(rng.integers(max(prompt_max // 8, 1),
                                          prompt_max + 1)),
                    int(rng.integers(out_min, out_max + 1)))
            for rid in range(n)]


def serve_phase(devices, cfg: ModelConfig, *, slots: int = 8,
                n_requests: int = 16, prompt_max: int = 128,
                out_min: int = 32, out_max: int = 64,
                max_seq_len: int = 1024, page_tokens: int = 128,
                seed: int = 0) -> dict:
    """Serve ``cfg`` at full depth through the paged engine + scheduler."""
    mesh = _mesh(devices[:1], (1, 1), ("data", "model"))
    model = build_model(cfg)
    # one KV page per allocation granule: no page padding
    page_bytes = kv_page_payload_elems(cfg, page_tokens) * 2
    plan = plan_kv_arena(cfg, mesh, page_tokens=page_tokens,
                         page_bytes=page_bytes, max_seqs=slots,
                         max_seq_len=max_seq_len)
    t0 = time.time()
    params = jax.jit(model.init)(jax.random.key(seed))
    engine = PagedDecodeEngine(model, mesh, plan, attn_impl="kernel")
    s = plan.max_seqs
    with mesh:
        compiled = engine.step.lower(
            engine.pages, params, jnp.asarray(engine.table.table),
            jnp.zeros((s,), jnp.int32), jnp.asarray(engine.slot_len),
            jnp.asarray(engine.slot_valid)).compile()
    kernel_in_step = "tpu_custom_call" in compiled.as_text()
    setup_s = time.time() - t0

    reqs = serve_requests(n_requests, prompt_max=prompt_max,
                          out_min=out_min, out_max=out_max, seed=seed)
    capture = {0, 1, n_requests - 1}
    t0 = time.time()
    res = ServeScheduler(engine, "continuous").run(params, reqs,
                                                   capture=capture)
    wall = time.time() - t0

    # reference: full-sequence forward of the same token streams, padded
    # to one length (causal: the padding cannot reach the checked position)
    vocab = model.cfg.vocab_size
    pad = -(-max(r.total_steps for r in reqs) // 128) * 128
    fwd = jax.jit(lambda p, t: model.forward(p, {"tokens": t}))
    errs = {}
    for r in reqs:
        if r.rid not in capture:
            continue
        toks = np.zeros((1, pad), np.int32)
        toks[0, :r.total_steps] = [request_token(r.rid, i, vocab)
                                   for i in range(r.total_steps)]
        ref = np.asarray(fwd(params, jnp.asarray(toks))[0, r.total_steps - 1],
                         np.float32)
        got = res["final_logits"][r.rid]
        errs[r.rid] = {"max_abs_err": float(np.abs(got - ref).max()),
                       "ref_max_abs": float(np.abs(ref).max())}
    max_err = max(e["max_abs_err"] for e in errs.values())
    tol = LOGIT_EPS * BF16_EPS * max(e["ref_max_abs"] for e in errs.values())
    on_tpu = devices[0].platform == "tpu"
    checks = {
        "kernel_in_step": kernel_in_step or not on_tpu,
        "all_requests_answered":
            res["generated_tokens"] == sum(r.decode_len for r in reqs),
        "several_slots_live": res["mean_live_slots"] > 1,
        "logits_match_forward": max_err <= tol,
    }
    return {
        "phase": "serve", "arch": cfg.name, "ok": all(checks.values()),
        "checks": checks, "layers": cfg.num_layers, "widths": _widths(cfg),
        "slots": slots, "requests": n_requests,
        "prompt_lens": [r.prompt_len for r in reqs],
        "decode_lens": [r.decode_len for r in reqs],
        "kv": {"page_tokens": page_tokens, "page_bytes": page_bytes,
               "pages": plan.n_kv_pages, "arena_bytes": plan.total_bytes,
               "kernel_kv_len": plan.max_blocks * page_tokens},
        "kernel_in_step": kernel_in_step,
        "setup_s": {"init_and_compile": setup_s},
        "wall_s": wall, "steps": res["steps"],
        "generated_tokens": res["generated_tokens"],
        "step_s_mean": wall / max(res["steps"], 1),
        "mean_live_slots": res["mean_live_slots"],
        "logit_errors": errs, "logit_max_abs_err": max_err,
        "logit_tol": tol,
    }


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


@jax.jit
def _leaf_gaps(a, b):
    """Per leaf of ``a`` and ``b``: ``|a-b|_2, |b|_2, max|a-b|, max|b|``,
    and the count of bitwise-equal elements."""
    gaps, equal = [], []
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        d = jnp.abs(x - y).ravel()
        gaps.append(jnp.stack([jnp.linalg.norm(d), jnp.linalg.norm(y.ravel()),
                               d.max(), jnp.abs(y).max()]))
        equal.append(jnp.sum(x == y))
    return gaps, equal


def moment_mismatch(a, b) -> dict:
    """``a`` against ``b`` (trees of like arrays), leaf by leaf: the largest
    ``|a - b|_2 / |b|_2`` (and the leaf that has it) and ``max|a - b| /
    max|b|`` over the leaves, and the share of elements bitwise equal."""
    gaps, equal = jax.device_get(_leaf_gaps(a, b))
    paths = [jax.tree_util.keystr(p)
             for p, _ in jax.tree_util.tree_leaves_with_path(a)]
    l2 = [_ratio(g[0], g[1]) for g in gaps]
    worst = int(np.argmax(l2))
    n = sum(x.size for x in jax.tree.leaves(a))
    return {"l2": l2[worst], "l2_leaf": paths[worst],
            "max": max(_ratio(g[2], g[3]) for g in gaps),
            "equal_share": sum(int(e) for e in equal) / n}


def _ratio(num, den) -> float:
    num, den = float(num), float(den)
    return num / den if den else (math.inf if num else 0.0)


@functools.partial(jax.jit, static_argnames=("t", "optim"))
def _adamw_gap(p0, p1, mu, nu, lr, *, t: int, optim: OptimConfig):
    f32 = jnp.float32
    decay = f32(1) - lr * f32(optim.weight_decay)
    c1, c2 = f32(1 - optim.b1 ** t), f32(1 - optim.b2 ** t)
    worst = []
    for a0, a1, m, v in zip(*(jax.tree.leaves(x) for x in (p0, p1, mu, nu)),
                            strict=True):
        upd = (m / c1) / (jnp.sqrt(v / c2) + f32(optim.eps))
        mag = jnp.abs(a0)
        ulp = jnp.nextafter(mag, f32(jnp.inf)) - mag
        worst.append(jnp.max(jnp.abs(a1 - (a0 * decay - lr * upd)) - 4 * ulp))
    return jnp.max(jnp.stack(worst)) / lr


def adamw_mismatch(p0, p1, mu, nu, *, lr: float, t: int,
                   optim: OptimConfig) -> float:
    """Largest ``|p1 - AdamW(p0; mu, nu)|`` over the elements, beyond 4 ulp
    of ``p0``, in units of ``lr``: how far the train step's update of
    ``p0`` is from the AdamW step of its own moments ``mu``/``nu`` (the
    moments after step ``t``), computed elementwise outside the step."""
    return float(_adamw_gap(p0, p1, mu, nu, jnp.float32(lr), t=t,
                            optim=optim))


def zero1_tree(shards, model, mesh, step_cfg: TrainStepConfig):
    """ZeRO-1 optimizer shards (one flat bucket each, split over the data
    axes) as a tree shaped like the parameters, by the step's own bucket
    plan.  The mesh has no model axis, so local shapes are global."""
    bucketer = build_comm(mesh, step_cfg).bucketer
    plan = bucketer.plan(model.abstract_params())
    return jax.jit(lambda bs: bucketer.debucketize(bs, plan))(list(shards))


def transport_mismatch(mesh, step_cfg: TrainStepConfig, sizes,
                       seed: int) -> float:
    """The step's transport against psum on random fp32 buckets, one per
    chip and size: the reduce-scatter's shards and their all-gather
    against psum's all-reduce.  Returns the largest ``|difference| / (eps32
    * sum_i |x_i|)`` over the elements (a sum in another order differs by
    at most ``world - 1`` of these)."""
    comm = build_comm(mesh, step_cfg)
    ref = Communicator(mesh, dataclasses.replace(comm.cfg, transport="psum"))
    (axis,) = comm.ordered_axes

    def gap(key, n):
        r = lax.axis_index(axis)
        x = jax.random.normal(jax.random.fold_in(key, r), (n,), jnp.float32)
        (shard,) = comm.reduce_scatter([x])
        (full,) = comm.all_gather([shard])
        (want,) = ref.all_reduce([x])
        (bound,) = ref.all_reduce([jnp.abs(x)])
        bound = FP32_EPS * bound + jnp.finfo(jnp.float32).tiny
        seg = n // comm.world
        mine = [lax.dynamic_slice_in_dim(v, r * seg, seg)
                for v in (want, bound)]
        worst = jnp.maximum(jnp.max(jnp.abs(full - want) / bound),
                            jnp.max(jnp.abs(shard - mine[0]) / mine[1]))
        return lax.pmax(worst, axis)

    worst = 0.0
    key = jax.random.key(seed)
    for n in sorted(set(sizes)):
        f = jax.jit(jax.shard_map(functools.partial(gap, n=n), mesh=mesh,
                                  in_specs=P(), out_specs=P(),
                                  check_vma=False))
        worst = max(worst, float(f(key)))
    return worst


def dp_phase(devices, cfg: ModelConfig, *, layers: int, seq: int = 2048,
             batch_per_chip: int = 1, seed: int = 0, log=print) -> dict:
    """ZeRO-1/ring_hier vs replicated/psum from the same state and data,
    at depth ``layers``, two steps each.

    First the transports alone: ring_hier's reduce-scatter and all-gather
    of random fp32 buckets of the step's sizes must give psum's all-reduce
    to the rounding bound of a sum in another order.  Then the train
    steps.  The repo's schedules give lr(0) = 0, so step 0 leaves the
    parameters as they were, and both runs take step 1's gradient at the
    initial parameters too.  Adam's first moment after step 1 is then
    linear in the two steps' clipped, reduced gradients and must agree
    leaf by leaf (``MOMENT_RTOL``).  Each run's parameters after step 1
    must be the AdamW update of its own moments, which checks the ZeRO-1
    all-gather that routes the updates back.  The two runs' updated
    parameters are not compared: an early Adam update is about +-lr
    whatever the gradient's size, so where a reduced gradient is within
    rounding of zero its sign is a coin toss."""
    n = len(devices)
    mesh = _mesh(devices, (n, 1), ("data", "model"))
    batch = batch_per_chip * n
    arch_cfg = _arch_step_cfg(2)
    psum_cfg = dataclasses.replace(
        arch_cfg, dp_mode="replicated",
        comm=dataclasses.replace(arch_cfg.comm, transport="psum"))
    cut = cfg.with_(num_layers=layers)
    model = build_model(cut)
    shape = ShapeConfig("smoke", seq, batch, "train")
    data = SyntheticTokens(DataConfig(vocab_size=model.cfg.vocab_size,
                                      seq_len=seq, global_batch=batch,
                                      seed=seed), model_cfg=cut)
    t0 = time.time()
    sizes = build_comm(mesh, arch_cfg).bucketer.plan(
        model.abstract_params()).bucket_sizes
    transport_err = transport_mismatch(mesh, arch_cfg, sizes, seed)
    transport_s = time.time() - t0
    log(f"[dp] {arch_cfg.comm.transport} vs psum on {len(set(sizes))} "
        f"bucket sizes: {transport_err:.3g} eps32 of sum|x| "
        f"({transport_s:.1f} s)")
    runs = {}
    # the replicated reference first: it has the larger peak, and takes the
    # chips before anything of the other run is held.  What is kept from
    # it waits on the host while the other run holds the chips
    for name, scfg in (("psum", psum_cfg), ("ring_hier", arch_cfg)):
        t0 = time.time()
        tr = Trainer(model, mesh, scfg, data, shape,
                     TrainerConfig(steps=2, log_every=1, seed=seed),
                     log=log)
        p0 = jax.device_get(tr.state["params"])
        hist = tr.run()["history"]
        s = time.time() - t0
        p1, mu, nu = (tr.state["params"], tr.state["opt"]["mu"],
                      tr.state["opt"]["nu"])
        del tr
        if scfg.dp_mode == "zero1":
            mu, nu = (zero1_tree(m, model, mesh, scfg) for m in (mu, nu))
        err = adamw_mismatch(p0, p1, mu, nu, lr=hist[1]["lr"], t=2,
                             optim=scfg.optim)
        runs[name] = {"dp_mode": scfg.dp_mode, "s": s, "update_err_lr": err,
                      "losses": [h["loss"] for h in hist],
                      "grad_norms": [h["grad_norm"] for h in hist],
                      "lrs": [h["lr"] for h in hist], "p0": p0,
                      "mu": jax.device_get(mu) if name == "psum" else mu}
        del p1, mu, nu
    a, b = runs["ring_hier"], runs["psum"]
    moments = moment_mismatch(a["mu"], b["mu"])
    same_init = all(np.array_equal(x, y) for x, y in
                    zip(jax.tree.leaves(a["p0"]), jax.tree.leaves(b["p0"])))
    n_params = sum(x.size for x in jax.tree.leaves(a["p0"]))

    def rel(u, v):
        return abs(u - v) / abs(v)

    checks = {
        "transports_agree": transport_err <= n - 1,
        "same_initial_params": same_init,
        "finite": _finite(a["losses"] + b["losses"] + a["grad_norms"]
                          + b["grad_norms"]),
        "lr0_zero_lr1_positive": a["lrs"][0] == b["lrs"][0] == 0
        and a["lrs"][1] == b["lrs"][1] > 0,
        # the loss is computed before any reduction: equal up to one fp32
        # ulp (the two programs may fuse the forward differently)
        "step0_losses_equal": abs(a["losses"][0] - b["losses"][0])
        <= float(np.spacing(np.float32(abs(a["losses"][0])))),
        "grad_norms_agree": all(rel(u, v) <= GNORM_RTOL for u, v in
                                zip(a["grad_norms"], b["grad_norms"])),
        "moments_agree": moments["l2"] <= MOMENT_RTOL
        and moments["max"] <= MOMENT_RTOL,
        "updates_match_adamw": max(a["update_err_lr"], b["update_err_lr"])
        <= UPDATE_ATOL_LR,
    }
    return {
        "phase": "dp", "arch": cfg.name, "ok": all(checks.values()),
        "checks": checks, "chips": n, "layers_kept": layers,
        "layers_total": cfg.num_layers, "widths": _widths(cfg), "seq": seq,
        "global_batch": batch, "params": n_params,
        "bucket_sizes": list(sizes),
        "transport_err_eps": transport_err, "transport_s": transport_s,
        "runs": {k: {kk: v[kk] for kk in ("dp_mode", "losses", "grad_norms",
                                          "lrs", "s", "update_err_lr")}
                 for k, v in runs.items()},
        "moment_mismatch": moments, "moment_rtol": MOMENT_RTOL,
        "update_atol_lr": UPDATE_ATOL_LR,
    }


def halo_phase(devices, *, local: int = 64, comps: int = 12,
               mass: float = 0.1, tol: float = 1e-6, maxiter: int = 500,
               seed: int = 0) -> dict:
    """2x2-decomposed CG against the single-device reference solve."""
    mesh = _mesh(devices[:4], (2, 2, 1), ("x", "y", "z"))
    op = StencilOp(specs=(HaloSpec("x", 0), HaloSpec("y", 1),
                          HaloSpec("z", 2)), mass=mass)
    comm = Communicator(mesh, CommConfig(transport="psum",
                                         data_axes=("x", "y", "z")))
    shape = (2 * local, 2 * local, local, comps)
    b = np.random.default_rng(seed).standard_normal(shape, np.float32)

    def run(bl):
        r = solve(op, bl, comm, solver="cg", tol=tol, maxiter=maxiter,
                  chunks=comm.halo_chunks)
        return r.x, r.iters, r.rel_residual

    spec = P("x", "y", "z", None)
    dist = jax.jit(jax.shard_map(run, mesh=mesh, in_specs=spec,
                                 out_specs=(spec, P(), P()),
                                 check_vma=False))
    ref = jax.jit(lambda bg: solve(op, bg, None, solver="cg", tol=tol,
                                   maxiter=maxiter, reference=True))
    t0 = time.time()
    x, iters, rel = jax.block_until_ready(
        dist(jax.device_put(b, NamedSharding(mesh, spec))))
    dist_s = time.time() - t0
    t0 = time.time()
    rr = jax.block_until_ready(ref(jax.device_put(b, devices[0])))
    ref_s = time.time() - t0
    x, xr = np.asarray(x), np.asarray(rr.x)
    rel_err = float(np.linalg.norm(x - xr) / np.linalg.norm(xr))
    checks = {
        "converged": float(rel) < tol and float(rr.rel_residual) < tol,
        "iters_within_one": abs(int(iters) - int(rr.iters)) <= 1,
        "solutions_agree": rel_err <= HALO_RTOL,
    }
    return {
        "phase": "halo", "ok": all(checks.values()), "checks": checks,
        "chips": 4, "decomposition": "2x2x1", "lattice": list(shape),
        "sites_per_chip": local ** 3, "solver": "cg", "mass": mass,
        "iters": int(iters), "ref_iters": int(rr.iters),
        "rel_residual": float(rel), "ref_rel_residual":
            float(rr.rel_residual), "solution_rel_err": rel_err,
        "first_call_incl_compile_s": {"distributed": dist_s,
                                      "reference": ref_s},
    }


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: train + serve on one chip; 4: the cross-chip "
                         "paths (dp, halo) on four")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: JAX found no devices: {e}", file=sys.stderr)
        return 2
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform!r} "
              f"devices; not running on anything else", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    print(json.dumps({"compile_cache": cache}), flush=True)

    cfg = get_config(ARCH)
    if args.chips == 1:
        phases = [lambda: train_phase(devices, cfg, seed=args.seed),
                  lambda: serve_phase(devices, cfg, seed=args.seed)]
    else:
        # halo first: it compiles in seconds, the dp programs in minutes
        phases = [lambda: halo_phase(devices[:4], seed=args.seed),
                  lambda: dp_phase(devices[:4], cfg, layers=DP_LAYERS,
                                   seed=args.seed)]
    ok = True
    t_all = time.time()
    for phase in phases:
        try:
            out = phase()
        except Exception as e:  # report the phase, then fail the run
            import traceback

            traceback.print_exc()
            out = {"phase": "error", "ok": False, "error": repr(e)}
        ok = ok and out["ok"]
        print(json.dumps(out, default=float), flush=True)
    print(json.dumps({"wall_s": time.time() - t_all}), flush=True)
    d = devices[0]
    print(json.dumps({"ok": ok, "device": {"platform": d.platform,
                                           "kind": d.device_kind,
                                           "count": len(devices)}}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

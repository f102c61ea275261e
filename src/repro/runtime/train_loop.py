"""Trainer: steps, metrics, checkpoint-restart, straggler accounting.

The fault-tolerance contract: every ``ckpt_every`` steps the full train
state is saved (atomically, async); on construction the trainer resumes
from the newest committed step.  Data is stateless-deterministic, so resume
== replay from the same step on any mesh that can hold the state.

Observability: with ``TrainerConfig.obs`` set, the trainer publishes onto a
:class:`repro.obs.MetricsBus` — phase spans (data / step: dispatch + wait /
ckpt), per-step gauges (step time, loss, grad norm, lr, MoE drop fraction),
straggler events (via the monitor's bus) — and, when a step-time prediction
is available (explicit, AOT roofline, or tuning-DB priced), feeds a
:class:`repro.obs.DriftDetector` so the live ``model_error`` gauge tracks
how far the latency model sits from the machine.  Obs is pure host-side
bookkeeping around the jitted step: it never changes what gets compiled.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.data import DataConfig, SyntheticTokens, make_batch_specs
from repro.models.model_api import Model
from repro.obs import ObsConfig, make_obs
from repro.runtime.ft import StragglerMonitor
from repro.runtime.train_step import (TrainStepConfig, _mesh_axes,
                                      build_step_schedule, build_train_step,
                                      init_train_state)


@dataclass
class TrainerConfig:
    steps: int = 100
    ckpt_every: int = 50
    ckpt_dir: str | None = None
    log_every: int = 10
    seed: int = 0
    obs: ObsConfig | None = None   # None -> NULL_OBS: zero-overhead no-op


class Trainer:
    def __init__(self, model: Model, mesh, step_cfg: TrainStepConfig,
                 data: SyntheticTokens, shape_cfg, tcfg: TrainerConfig,
                 log: Callable[[str], None] = print):
        self.model = model
        self.mesh = mesh
        self.step_cfg = step_cfg
        self.data = data
        self.tcfg = tcfg
        self.log = log
        self.obs = make_obs(tcfg.obs)
        self.monitor = StragglerMonitor(bus=self.obs.bus)
        self.ckpt = (CheckpointManager(tcfg.ckpt_dir)
                     if tcfg.ckpt_dir else None)

        batch_specs = make_batch_specs(model.cfg, shape_cfg, mesh)
        with mesh:
            self.step_fn = build_train_step(model, mesh, step_cfg, batch_specs)
            state, self.state_specs = init_train_state(
                model, mesh, step_cfg, key=jax.random.key(tcfg.seed))
        self.state = state
        self.start_step = 0
        if self.ckpt is not None:
            try:
                restored, step = self.ckpt.restore_latest(self.state)
            except ValueError as e:
                if "strict=False" not in str(e):
                    raise
                # structural change (e.g. toggling use_arena's scratch comm
                # buffer): retry path-matched, loudly — leaves absent from
                # the checkpoint keep their fresh-init values
                restored, step = self.ckpt.restore_latest(self.state,
                                                          strict=False)
                self.log(f"[trainer] state structure changed since the "
                         f"checkpoint; resumed by path matching ({e})")
            if restored is not None:
                self.state = restored
                self.start_step = int(step)
                self.log(f"[trainer] resumed from step {step}")
        self.drift = self._init_drift()

    def _init_drift(self):
        """Wire a DriftDetector when the obs config carries (or asks us to
        compute) a step-time prediction; None otherwise."""
        cfg = self.tcfg.obs
        if not self.obs.enabled or cfg is None:
            return None
        if cfg.predicted_step_s is not None:
            return self.obs.drift_detector(cfg.predicted_step_s,
                                           source="explicit")
        if not (cfg.predict or cfg.tuned_db):
            return None
        from repro.obs import predict as obs_predict

        latency = None
        source = "roofline"
        if cfg.tuned_db:
            data_axes, _ = _mesh_axes(self.mesh)
            ccfg = self.step_cfg.comm_config(data_axes)
            mesh_label = "x".join(
                str(d) for d in self.mesh.devices.shape)
            got = obs_predict.tuned_latency(
                cfg.tuned_db, transport=ccfg.transport,
                mesh_label=mesh_label, channels=ccfg.channels,
                page_bytes=ccfg.page_bytes)
            if got is not None:
                latency, fit_err, key = got
                source = "tuned"
                self.obs.event("tuned_record", key=key, **fit_err)
        sched = build_step_schedule(self.model, self.mesh, self.step_cfg)
        pred = obs_predict.predict_step_time(
            self.step_fn, (self.state, self.data.batch_at(0)),
            mesh=self.mesh, overlap_fraction=sched.overlap_fraction,
            latency=latency)
        self.obs.event("prediction", **pred)
        self.log(f"[obs] predicted step {pred['t_step_s']*1e3:.1f} ms "
                 f"({pred['bottleneck']}-bound, {pred['source']})")
        return self.obs.drift_detector(pred["t_step_s"], source=source)

    def run(self) -> dict:
        history: list[dict] = []
        obs = self.obs
        t_total = time.time()
        for step in range(self.start_step, self.tcfg.steps):
            with obs.span("data", step=step):
                batch = self.data.batch_at(step)
            t0 = time.time()
            with obs.span("step", step=step):
                with obs.span("dispatch", step=step):
                    with self.mesh:
                        self.state, metrics = self.step_fn(self.state, batch)
                with obs.span("wait", step=step) as sp:
                    sp.fence(metrics)
                    loss = float(metrics["loss"])   # blocks on completion
            dt = time.time() - t0
            ev = self.monitor.record(step, dt)
            obs.counter("steps")
            obs.gauge("step_time_s", dt)
            obs.gauge("loss", loss)
            obs.gauge("grad_norm", float(metrics["grad_norm"]))
            obs.gauge("lr", float(metrics["lr"]))
            if "moe_drop_fraction" in metrics:
                obs.gauge("moe_drop_fraction",
                          float(metrics["moe_drop_fraction"]))
            if self.drift is not None:
                self.drift.update(step, dt)
            rec = {"step": step, "loss": loss,
                   "grad_norm": float(metrics["grad_norm"]),
                   "lr": float(metrics["lr"]), "sec": dt,
                   "straggler": bool(ev)}
            history.append(rec)
            if step % self.tcfg.log_every == 0 or step == self.tcfg.steps - 1:
                self.log(f"[train] step {step:5d} loss {loss:.4f} "
                         f"gnorm {rec['grad_norm']:.3f} lr {rec['lr']:.2e} "
                         f"{dt*1e3:.0f} ms" + (" STRAGGLER" if ev else ""))
            if self.ckpt is not None and (step + 1) % self.tcfg.ckpt_every == 0:
                with obs.span("ckpt", step=step):
                    self.ckpt.save(self.state, step + 1)
        if self.ckpt is not None:
            with obs.span("ckpt", step=self.tcfg.steps):
                self.ckpt.save(self.state, self.tcfg.steps)
                self.ckpt.wait()
        wall = time.time() - t_total
        obs.event("run_done", steps=self.tcfg.steps - self.start_step,
                  wall_s=wall, stragglers=len(self.monitor.events),
                  drifting=bool(self.drift.drifting) if self.drift else False)
        paths = obs.finish()
        return {"history": history, "wall": wall,
                "straggler_events": self.monitor.events,
                "obs": paths}

"""End-to-end behaviour: the Trainer trains, checkpoints, and resumes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import AxisType
from repro.configs import reduced_config
from repro.core.reducer import ReduceConfig
from repro.data import DataConfig, SyntheticTokens
from repro.models import build_model
from repro.optim import OptimConfig
from repro.runtime.train_loop import Trainer, TrainerConfig
from repro.runtime.train_step import TrainStepConfig


def _mesh():
    # feature-detects AxisType / axis_types support for the installed jax
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def _setup(tmp_path, steps=24, ckpt_every=8):
    cfg = reduced_config("llama3.2-1b")
    model = build_model(cfg)
    from repro.configs.base import ShapeConfig

    shape = ShapeConfig("tiny", 64, 4, "train")
    data = SyntheticTokens(DataConfig(vocab_size=model.cfg.vocab_size,
                                      seq_len=64, global_batch=4, seed=1),
                           model_cfg=cfg)
    scfg = TrainStepConfig(
        dp_mode="replicated",
        reduce=ReduceConfig(policy="fused_ring_hierarchical"),
        optim=OptimConfig(base_lr=3e-3, warmup=5, total_steps=steps),
        microbatches=1)
    tcfg = TrainerConfig(steps=steps, ckpt_every=ckpt_every,
                         ckpt_dir=str(tmp_path / "ckpt"), log_every=100)
    return model, shape, data, scfg, tcfg


def test_training_reduces_loss(tmp_path):
    model, shape, data, scfg, tcfg = _setup(tmp_path)
    tr = Trainer(model, _mesh(), scfg, data, shape, tcfg,
                 log=lambda s: None)
    out = tr.run()
    hist = out["history"]
    first = np.mean([h["loss"] for h in hist[:4]])
    last = np.mean([h["loss"] for h in hist[-4:]])
    assert np.isfinite(first) and np.isfinite(last)
    assert last < first - 0.05, f"no learning: {first:.4f} -> {last:.4f}"


def test_checkpoint_restart_is_seamless(tmp_path):
    """Kill after N steps; a fresh Trainer resumes and matches an unbroken
    run exactly (deterministic data + state restore)."""
    model, shape, data, scfg, tcfg = _setup(tmp_path, steps=12, ckpt_every=4)

    # unbroken reference
    import dataclasses

    ref_dir = tmp_path / "ref"
    tcfg_ref = dataclasses.replace(tcfg, ckpt_dir=str(ref_dir))
    ref = Trainer(model, _mesh(), scfg, data, shape, tcfg_ref,
                  log=lambda s: None).run()

    # crashed run: stop at step 8 (simulated failure after a commit)
    tcfg_a = dataclasses.replace(tcfg, steps=8)
    Trainer(model, _mesh(), scfg, data, shape, tcfg_a, log=lambda s: None).run()
    # resume to completion
    tr_b = Trainer(model, _mesh(), scfg, data, shape, tcfg, log=lambda s: None)
    assert tr_b.start_step == 8, "did not resume from the committed step"
    out_b = tr_b.run()

    ref_tail = {h["step"]: h["loss"] for h in ref["history"]}
    for h in out_b["history"]:
        assert abs(h["loss"] - ref_tail[h["step"]]) < 1e-4, \
            f"divergence at step {h['step']}"


def test_straggler_events_surface(tmp_path):
    model, shape, data, scfg, tcfg = _setup(tmp_path, steps=6)
    tr = Trainer(model, _mesh(), scfg, data, shape, tcfg, log=lambda s: None)
    for i in range(5):
        assert not tr.monitor.record(i, 0.1)
    ev = tr.monitor.record(5, 1.0)
    # bool-compat: the event is truthy exactly when flagged
    assert ev and ev.flagged and bool(ev) is True
    assert ev.step == 5 and ev.seconds == 1.0
    assert ev.ewma == pytest.approx(0.1) and ev.ratio == pytest.approx(10.0)
    assert len(tr.monitor.events) == 1
    assert tr.monitor.events[0] is ev


def test_straggler_event_structure_and_warmup():
    from repro.runtime.ft import StragglerEvent, StragglerMonitor

    m = StragglerMonitor(warmup_steps=2)
    w = m.record(0, 5.0)   # compile step: collected, never flagged
    assert isinstance(w, StragglerEvent)
    assert not w and w.ewma == 0.0 and w.ratio == float("inf")
    m.record(1, 0.1)       # ewma seeds from median(5.0, 0.1)
    assert not m.record(2, 0.2)
    assert m.events == []


def test_heartbeat_dead_hosts_boundary_and_self_exclusion(tmp_path):
    from repro.runtime.ft import Heartbeat

    d = str(tmp_path / "beats")
    a = Heartbeat(d, "a", timeout=10.0)
    b = Heartbeat(d, "b", timeout=10.0)
    a.beat(now=100.0)
    b.beat(now=100.0)
    # exactly at the timeout is still alive (strict >)
    assert a.dead_hosts(now=110.0) == []
    # one tick past: dead — but only as seen by the *other* host; a host
    # never reports itself dead off its own stale file
    assert a.dead_hosts(now=110.1) == ["b"]
    assert b.dead_hosts(now=110.1) == ["a"]
    b.beat(now=111.0)
    assert a.dead_hosts(now=112.0) == []


def test_heartbeat_prune_stale_cleans_beat_files(tmp_path):
    from repro.runtime.ft import Heartbeat

    d = str(tmp_path / "beats")
    a = Heartbeat(d, "a", timeout=1.0)
    b = Heartbeat(d, "b", timeout=1.0)
    a.beat(now=0.0)
    b.beat(now=0.0)
    # within grace: dead but not pruned
    assert a.prune_stale(now=5.0) == []
    assert a.dead_hosts(now=5.0) == ["b"]
    # past grace (default 10x timeout): the stale file is removed...
    assert a.prune_stale(now=11.0) == ["b"]
    assert a.dead_hosts(now=11.0) == []
    # ...but never the reporter's own file
    assert a.prune_stale(now=1e9) == []
    assert (tmp_path / "beats" / "a.beat").exists()
    assert not (tmp_path / "beats" / "b.beat").exists()

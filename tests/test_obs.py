"""repro.obs: bus/tracer/drift units, the instrumented-train acceptance
run, and the obs-off HLO-identity pin."""

import json

import numpy as np
import pytest

import jax
from jax.sharding import AxisType
from repro.configs import reduced_config
from repro.configs.base import ShapeConfig
from repro.core.reducer import ReduceConfig
from repro.data import DataConfig, SyntheticTokens
from repro.models import build_model
from repro.obs import (DriftDetector, MetricsBus, NULL_OBS, ObsConfig,
                       Tracer, make_obs)
from repro.obs import report as obs_report
from repro.obs import schema as obs_schema
from repro.optim import OptimConfig
from repro.runtime.train_loop import Trainer, TrainerConfig
from repro.runtime.train_step import TrainStepConfig


# ---------------------------------------------------------------------------
# bus
# ---------------------------------------------------------------------------


def test_bus_aggregates_and_reads():
    bus = MetricsBus()
    assert bus.counter("steps") == 1.0
    assert bus.counter("steps", 2.0) == 3.0
    bus.counter("stall", reason="a")
    bus.counter("stall", reason="b")
    assert bus.counter_value("stall", reason="a") == 1.0
    assert bus.counter_value("stall") == 0.0       # labels are part of the key
    assert bus.counter_total("stall") == 2.0
    bus.gauge("loss", 3.5)
    bus.gauge("loss", 2.5)
    assert bus.gauge_value("loss") == 2.5          # last value wins
    assert bus.has_gauge("loss") and not bus.has_gauge("nope")
    for v in (1.0, 2.0, 3.0, 4.0):
        bus.observe("lat", v)
    h = bus.hist_summary("lat")
    assert h["count"] == 4 and h["min"] == 1.0 and h["max"] == 4.0
    s = bus.summary()
    assert s["counters"]["stall{reason=a}"] == 1.0
    assert s["n_records"] == bus.n_records > 0


def test_bus_jsonl_sink_and_numpy_coercion(tmp_path):
    d = str(tmp_path / "run")
    bus = MetricsBus(d, flush_every=2)
    bus.gauge("g", np.float32(1.5))                 # numpy scalar must encode
    bus.event("ev", arr=np.int64(7), s="x")
    bus.counter("c")
    bus.close()
    lines = [json.loads(l) for l in open(bus.path) if l.strip()]
    assert [r["kind"] for r in lines] == ["gauge", "event", "counter"]
    assert lines[0]["value"] == 1.5
    assert lines[1]["fields"]["arr"] == 7
    assert all(isinstance(r["ts"], float) for r in lines)


def test_null_bus_is_inert(tmp_path):
    obs = make_obs(None)
    assert obs is NULL_OBS and not obs.enabled
    obs.counter("x")
    obs.gauge("y", 1.0)
    with obs.span("phase") as sp:
        sp.fence([1, 2])
    assert obs.bus.counter_total("x") == 0.0
    assert obs.drift_detector(1.0) is None
    assert obs.finish() == {"events": None, "trace": None}
    assert make_obs(ObsConfig.off()) is NULL_OBS


# ---------------------------------------------------------------------------
# tracer / chrome export
# ---------------------------------------------------------------------------


def test_tracer_spans_mirror_to_bus_and_export_chrome(tmp_path):
    bus = MetricsBus()
    clock = iter(np.arange(0.0, 10.0, 0.5))
    tr = Tracer(bus, clock=lambda: float(next(clock)), pid=7, tid=1)
    with tr.span("step", step=0):
        with tr.span("wait"):
            pass
    assert [e[0] for e in tr.events] == ["wait", "step"]
    assert bus.spans["step"][0] == pytest.approx(1.5)   # 3 clock reads inside
    path = tr.export_chrome(str(tmp_path / "trace.json"))
    doc = json.load(open(path))
    evs = doc["traceEvents"]
    assert len(evs) == 2
    for e in evs:
        assert e["ph"] == "X" and e["dur"] > 0 and e["pid"] == 7
        assert set(e) >= {"name", "ts", "dur", "pid", "tid"}
    assert {e["name"] for e in evs} == {"step", "wait"}
    assert evs[1]["args"] == {"step": 0}


def test_disabled_tracer_hands_out_the_shared_null_span():
    tr = Tracer(enabled=False)
    s1, s2 = tr.span("a"), tr.span("b", x=1)
    assert s1 is s2
    with s1:
        pass
    assert tr.events == []


def test_span_fence_blocks_on_device_work():
    import jax.numpy as jnp

    bus = MetricsBus()
    tr = Tracer(bus)
    with tr.span("wait") as sp:
        y = sp.fence(jnp.ones((8, 8)) @ jnp.ones((8, 8)))
    assert float(y[0, 0]) == 8.0
    assert bus.spans["wait"][0] > 0


def test_span_enters_the_profiler_trace_as_repro_name(tmp_path):
    """Under ``jax.profiler`` a span is a ``repro.<name>`` event on the
    host plane (the device ops' clock), nested in the annotation around
    it and carrying its labels; NULL_OBS emits nothing."""
    from conftest import host_trace_events

    obs = make_obs(ObsConfig(run_dir=None))

    def run():
        with jax.profiler.TraceAnnotation("outer"):
            with obs.span("serve.stage", step=3, slot="a"):
                with NULL_OBS.span("silent", step=4):
                    pass

    evs = host_trace_events(run, tmp_path / "trace")
    (outer,) = [e for e in evs if e[0] == "outer"]
    (stage,) = [e for e in evs if e[0] == "repro.serve.stage"]
    assert outer[1] <= stage[1] < stage[2] <= outer[2]
    assert stage[3]["step"] == 3 and stage[3]["slot"] == "a"
    assert not [e for e in evs if "silent" in e[0]]
    # the bus mirror stays, under the span's own name
    assert len(obs.bus.spans["serve.stage"]) == 1


def test_obs_counts_each_new_executable():
    """``compiles`` counts every executable obtained while the Obs is
    live: a new program counts once, a cached call never, and nothing
    counts after ``finish`` or under NULL_OBS."""
    import jax.numpy as jnp

    obs = make_obs(ObsConfig(run_dir=None))
    f = jax.jit(lambda x: jnp.cos(x) * 3.0 + 1.0)
    x = jnp.arange(5.0)
    f(x).block_until_ready()
    n = obs.bus.counter_value("compiles", fun="jit(<lambda>)")
    assert n == 1
    f(x).block_until_ready()
    f.lower(x).compile()                       # the same executable
    assert obs.bus.counter_value("compiles", fun="jit(<lambda>)") == n
    f(jnp.arange(6.0)).block_until_ready()     # a new shape compiles
    assert obs.bus.counter_value("compiles", fun="jit(<lambda>)") == n + 1
    total = obs.bus.counter_total("compiles")
    obs.finish()
    jax.jit(lambda x: x - 2.0)(x).block_until_ready()
    assert obs.bus.counter_total("compiles") == total
    assert NULL_OBS.bus.counter_total("compiles") == 0.0


# ---------------------------------------------------------------------------
# drift detection
# ---------------------------------------------------------------------------


def test_drift_detector_warmup_window_and_alarm_transition():
    bus = MetricsBus()
    det = DriftDetector(0.1, bus=bus, threshold=0.5, window=4, warmup=1,
                        min_samples=2)
    s0 = det.update(0, 10.0)       # compile step: gauged, excluded
    assert s0.warmup and not s0.drifting and s0.median_rel_err is None
    assert bus.gauge_value("model_error", metric="step_time_s") \
        == pytest.approx(99.0)
    assert not det.update(1, 0.11).drifting     # window not full yet
    s2 = det.update(2, 0.12)
    assert s2.median_rel_err == pytest.approx(0.15) and not s2.drifting
    # sustained 2x steps: the rolling median crosses, alarm fires ONCE
    for step in (3, 4, 5):
        det.update(step, 0.2)
    assert det.drifting and det.alarms == 1
    assert bus.counter_total("drift_alarms") == 1.0
    det.update(6, 0.2)             # still drifting: no second alarm
    assert det.alarms == 1
    # recovery: back near the prediction clears the state...
    for step in (7, 8, 9, 10):
        det.update(step, 0.1)
    assert not det.drifting
    # ...and a relapse alarms again (transition counting)
    for step in (11, 12, 13, 14):
        det.update(step, 0.25)
    assert det.alarms == 2


def test_drift_detector_rejects_nonpositive_prediction():
    with pytest.raises(ValueError, match="predicted_s"):
        DriftDetector(0.0)


def test_one_straggler_step_cannot_fire_the_alarm():
    det = DriftDetector(0.1, threshold=0.5, window=5, warmup=0,
                        min_samples=3)
    for step in range(4):
        det.update(step, 0.1)
    det.update(4, 5.0)             # one GC pause / straggler
    assert not det.drifting and det.alarms == 0


# ---------------------------------------------------------------------------
# bench schema
# ---------------------------------------------------------------------------


def test_rows_from_csv_headers_blocks_and_degradation():
    text = """# commentary
a,b,c
1,2.5,x

name,us
ring,12.0
ring,13.5
9,9,9,9
"""
    rows = obs_schema.rows_from_csv(text)
    assert rows[0] == {"a": 1, "b": 2.5, "c": "x"}
    assert rows[1] == {"name": "ring", "us": 12.0}
    assert rows[2] == {"name": "ring", "us": 13.5}
    # shape change without a new header degrades to positional keys
    assert rows[3] == {"col0": 9, "col1": 9, "col2": 9, "col3": 9}


def test_bench_record_roundtrip_and_validation(tmp_path):
    rows = [{"transport": "ring", "us": 10.5}]
    path = obs_schema.write_bench_record(str(tmp_path), "allreduce", rows,
                                         meta={"dry": True})
    assert path.endswith("BENCH_allreduce.json")
    rec = obs_schema.load_bench_record(path)
    assert rec["schema"] == obs_schema.SCHEMA
    assert rec["rows"] == rows and rec["n_rows"] == 1
    with pytest.raises(ValueError, match="schema"):
        obs_schema.validate_record({"schema": "nope"})
    with pytest.raises(ValueError, match="scalar"):
        obs_schema.bench_record("x", [{"bad": [1, 2]}])


# ---------------------------------------------------------------------------
# the acceptance run: 2 instrumented steps -> events + trace + report
# ---------------------------------------------------------------------------


def _mesh():
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def _tiny(steps, obs_cfg):
    cfg = reduced_config("llama3.2-1b")
    model = build_model(cfg)
    shape = ShapeConfig("tiny", 64, 4, "train")
    data = SyntheticTokens(DataConfig(vocab_size=model.cfg.vocab_size,
                                      seq_len=64, global_batch=4, seed=1),
                           model_cfg=cfg)
    scfg = TrainStepConfig(
        dp_mode="replicated",
        reduce=ReduceConfig(policy="fused_ring_hierarchical"),
        optim=OptimConfig(base_lr=3e-3, warmup=5, total_steps=steps),
        microbatches=1)
    tcfg = TrainerConfig(steps=steps, ckpt_every=1000, log_every=100,
                         obs=obs_cfg)
    return Trainer(model, _mesh(), scfg, data, shape, tcfg,
                   log=lambda s: None)


def test_instrumented_train_produces_events_trace_and_drift(tmp_path,
                                                            capsys):
    run_dir = str(tmp_path / "run")
    # predicted_step_s far below reality => guaranteed drift within 2 steps
    obs_cfg = ObsConfig(run_dir=run_dir, predicted_step_s=1e-7,
                        drift_warmup=0, drift_min_samples=1, drift_window=4)
    tr = _tiny(2, obs_cfg)
    out = tr.run()
    assert out["obs"]["events"] and out["obs"]["trace"]

    records = obs_report.read_events(run_dir)
    kinds = {r["kind"] for r in records}
    assert {"span", "gauge", "counter", "event"} <= kinds
    span_names = {r["name"] for r in records if r["kind"] == "span"}
    assert {"data", "step", "dispatch", "wait"} <= span_names
    gauge_names = {r["name"] for r in records if r["kind"] == "gauge"}
    assert {"step_time_s", "loss", "grad_norm", "lr",
            "model_error"} <= gauge_names
    assert any(r["name"] == "drift_alarm" for r in records
               if r["kind"] == "event")

    # Perfetto-loadable: valid JSON, >= 1 complete ("X") event
    doc = json.load(open(out["obs"]["trace"]))
    assert doc["traceEvents"] and all(e["ph"] == "X"
                                      for e in doc["traceEvents"])
    assert sum(1 for e in doc["traceEvents"] if e["name"] == "step") == 2

    # the report renders from the files alone
    assert obs_report.main([run_dir]) == 0
    text = capsys.readouterr().out
    assert "per-phase time breakdown" in text
    assert "predicted vs measured (drift)" in text
    summary = obs_report.summarize(run_dir)
    assert summary["counters"]["steps"] == 2.0
    assert len(summary["drift"]["samples"]) == 2
    assert summary["trace"]["n_events"] == len(doc["traceEvents"])


def test_obs_off_lowers_to_identical_hlo(tmp_path):
    """The acceptance pin: ObsConfig(enabled=False) — and obs entirely —
    must not perturb the compiled step program."""
    tr_off = _tiny(2, ObsConfig.off())
    tr_none = _tiny(2, None)
    tr_on = _tiny(2, ObsConfig(run_dir=str(tmp_path / "r"),
                               predicted_step_s=1.0))
    batch = tr_on.data.batch_at(0)
    texts = []
    for tr in (tr_off, tr_none, tr_on):
        with tr.mesh:
            texts.append(tr.step_fn.lower(tr.state, batch).as_text())
    assert texts[0] == texts[1] == texts[2]


def test_predict_step_time_prices_the_live_step(monkeypatch):
    from repro.comm import plan
    from repro.obs.predict import predict_step_time
    from repro.runtime.train_step import build_step_schedule

    tr = _tiny(2, None)
    sched = build_step_schedule(tr.model, tr.mesh, tr.step_cfg)
    # the CPU has no published peaks: price it as a v5e for this test
    kind = tr.mesh.devices.flat[0].device_kind
    monkeypatch.setitem(plan.DEVICE_PEAKS, kind, plan.V5E)
    pred = predict_step_time(tr.step_fn, (tr.state, tr.data.batch_at(0)),
                             mesh=tr.mesh,
                             overlap_fraction=sched.overlap_fraction)
    assert pred["t_step_s"] > 0 and pred["source"] == "roofline"
    assert pred["bottleneck"] in ("compute", "memory", "collective")
    assert pred["t_step_s"] >= pred["t_exposed_collective_s"]
    assert pred["device_kind"] == kind


def test_predict_step_time_raises_for_a_device_without_peaks():
    """No silent v5e default: the live device (here the CPU) must be in
    the peaks table, and a requested prediction that fails stops the
    Trainer instead of being logged and skipped."""
    from repro.obs.predict import predict_step_time

    tr = _tiny(2, None)
    with pytest.raises(ValueError, match="no published peaks"):
        predict_step_time(tr.step_fn, (tr.state, tr.data.batch_at(0)),
                          mesh=tr.mesh)
    with pytest.raises(ValueError, match="no published peaks"):
        _tiny(2, ObsConfig(predict=True))


# ---------------------------------------------------------------------------
# report CLI edges
# ---------------------------------------------------------------------------


def test_report_missing_run_dir_raises(tmp_path):
    with pytest.raises(FileNotFoundError, match="events.jsonl"):
        obs_report.read_events(str(tmp_path))


def test_report_json_mode(tmp_path, capsys):
    d = str(tmp_path / "r")
    obs = make_obs(ObsConfig(run_dir=d, flush_every=1))
    obs.counter("steps")
    obs.gauge("loss", 1.25)
    obs.finish()
    assert obs_report.main([d, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["counters"]["steps"] == 1.0 and doc["gauges"]["loss"] == 1.25

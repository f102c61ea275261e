"""Training window: the program's ``Trainer`` and its compiled step.

Set-up builds one ``Trainer`` (its ``step_fn`` and state, with the arch's
own ``settings_for`` data-parallel mode and transport), replaces its
initial parameters with the benchmark's seeded weights, and drives the
first ``checked_steps`` steps through the window's own call.  What the
check needs from those steps is read as they pass: each step's loss, the
gradient the optimizer took in the first step (from Adam's first moment),
and each leaf's change after them.  The window then goes on with the same
object.  A step is: make the batch, dispatch, read the loss back (as
``Trainer.run`` does).

The check replays the same steps on the plain reference and compares, leaf
by leaf (see ``compare``).
"""

from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from bench import flops, gen, weights
from bench.drivers import common

LEAF_FLOOR = 1e-3   # leaves with a gradient under this share of the median
                    # leaf's move by round-off alone under Adam


def compare(prog: dict, ref: dict) -> dict:
    """The three numbers held to their limits.

    * ``loss_gap``: the largest |loss - reference loss| over the checked
      steps (nats);
    * ``grad_gap``: over the leaves, the largest gap between the norms of
      the first step's gradient, over the reference leaf's norm or the
      median leaf's, whichever is larger;
    * ``update_gap``: the same for each leaf's change after the checked
      steps, leaving out leaves whose reference gradient is under
      ``LEAF_FLOOR`` of the median leaf's.
    """
    def worst(p, r, keep):
        p, r = np.asarray(p, np.float64), np.asarray(r, np.float64)
        floor = np.median(r[keep]) if keep.any() else 0.0
        gap = np.abs(p - r) / np.maximum(np.maximum(r, floor), 1e-30)
        gap = np.where(keep, gap, 0.0)
        i = int(np.argmax(gap))
        return float(gap[i]), i

    g_ref = np.asarray(ref["grad_norms"], np.float64)
    keep_all = np.ones_like(g_ref, bool)
    keep = g_ref >= LEAF_FLOOR * np.median(g_ref)
    losses = [abs(a - b) for a, b in zip(prog["losses"], ref["losses"],
                                         strict=True)]
    grad_gap, gi = worst(prog["grad_norms"], g_ref, keep_all)
    upd_gap, ui = worst(prog["change_norms"], ref["change_norms"], keep)
    return {"loss_gap": max(losses) if all(map(math.isfinite, losses))
            else math.inf,
            "grad_gap": grad_gap, "update_gap": upd_gap,
            "_worst_leaves": {"grad": gi, "update": ui},
            "_left_out": int((~keep).sum())}


@jax.jit
def _leaf_norms(tree):
    return jnp.stack([jnp.linalg.norm(x.reshape(-1).astype(jnp.float32))
                      for x in jax.tree.leaves(tree)])


@jax.jit
def _diff_norms(a, b):
    return jnp.stack([jnp.linalg.norm((x - y).reshape(-1)) for x, y in
                      zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True)])


class Driver(common.Driver):
    step_span = "step"

    def setup(self):
        from repro.configs.base import ShapeConfig
        from repro.launch.settings import settings_for
        from repro.optim import OptimConfig
        from repro.runtime.train_loop import Trainer, TrainerConfig
        from repro.runtime.train_step import TrainStepConfig, build_comm

        cfg, mix = self.cfg, self.mix
        n = self.cell["chips"]
        self.mesh = jax.make_mesh((n, 1), ("data", "model"),
                                  axis_types=(jax.sharding.AxisType.Auto,) * 2,
                                  devices=self.devices[:n])
        self.model = common.program_model(cfg)
        weights.check_layout(cfg, self.model.abstract_params())
        st = settings_for(cfg["program_arch"])
        self.optim = OptimConfig(**mix["optim"])
        step_cfg = TrainStepConfig(dp_mode=st.dp_mode, comm=st.comm_config(),
                                   optim=self.optim,
                                   microbatches=mix["microbatches"])
        self.step_cfg = step_cfg
        shape = ShapeConfig("bench", mix["seq_len"], mix["global_batch"],
                            "train")
        self.trainer = Trainer(self.model, self.mesh, step_cfg, None, shape,
                               TrainerConfig(steps=0, log_every=1 << 30),
                               log=self.log)
        state = self.trainer.state
        shard = jax.tree.map(lambda a: a.sharding, state["params"])
        state["params"] = None
        state["params"] = weights.make_params(cfg, self.seed, sharding=shard)
        self.batch_sharding = NamedSharding(self.mesh, P("data", None))
        self.vocab = cfg["vocab_size"]
        self.step_no = 0
        self.tokens_per_step = mix["global_batch"] * mix["seq_len"]
        self.losses: list[float] = []
        # the first steps: through the window's own call, readings taken
        # between them (their device time is the check's, not set-up's)
        for k in range(mix["checked_steps"]):
            self.losses.append(self.step())
            if k == 0:
                t0 = time.perf_counter()
                self.grad_norms = self._first_grad_norms(build_comm)
                self.excluded_s += time.perf_counter() - t0
        t0 = time.perf_counter()
        p0 = weights.make_params(cfg, self.seed, sharding=shard)
        self.change_norms = np.asarray(_diff_norms(
            self.trainer.state["params"], p0)).tolist()
        del p0
        self.excluded_s += time.perf_counter() - t0

    def _first_grad_norms(self, build_comm) -> list[float]:
        """The clipped gradient of step 0, leaf by leaf: Adam's first
        moment after one step is ``(1 - b1) * g``."""
        mu = self.trainer.state["opt"]["mu"]
        scale = 1.0 / (1.0 - self.optim.b1)
        if self.step_cfg.dp_mode == "zero1":
            comm = build_comm(self.mesh, self.step_cfg)
            plan = comm.bucketer.plan(self.model.abstract_params())
            norms = jax.jit(lambda bs: _leaf_norms(
                comm.bucketer.debucketize(bs, plan)))(list(mu))
        else:
            norms = _leaf_norms(mu)
        return (np.asarray(norms, np.float64) * scale).tolist()

    def batch(self, i: int):
        toks, labels = gen.train_batch(self.mix, self.vocab, self.seed, i)
        if self.fault in ("half_batch", "no_exchange"):
            toks, labels = (planted(x, self.fault, self.cell["chips"])
                            for x in (toks, labels))
        put = lambda x: jax.device_put(x, self.batch_sharding)  # noqa: E731
        return {"tokens": put(toks), "labels": put(labels)}

    def step(self) -> float:
        with jax.profiler.TraceAnnotation("bench.step"):
            with jax.profiler.TraceAnnotation("bench.data"):
                batch = self.batch(self.step_no)
            if self.fault == "stale_state":
                kept = jax.tree.map(jnp.copy, self.trainer.state)
            with self.mesh:
                state, metrics = self.trainer.step_fn(self.trainer.state, batch)
            self.trainer.state = kept if self.fault == "stale_state" else state
            with jax.profiler.TraceAnnotation("bench.wait"):
                loss = float(metrics["loss"])
        self.step_no += 1
        return loss

    def window(self, seconds: float) -> dict:
        t0 = time.perf_counter()
        steps, bad = 0, 0
        while True:
            loss = self.step()
            steps += 1
            bad += not math.isfinite(loss)
            t1 = time.perf_counter()
            if t1 - t0 >= seconds:
                break
        return {"metrics": {"train_tokens_per_s":
                            steps * self.tokens_per_step / (t1 - t0)},
                "attempted": steps, "failed": bad}

    def traced_steps(self):
        for _ in range(self.mix["trace_steps"]):
            self.step()

    def layer_inputs(self, red: dict) -> dict:
        return {"step_flops": flops.train_step_flops(self.cfg, self.mix)}

    def release(self):
        self.trainer = None
        super().release()

    def _reference(self, prec: str = "float32", fault: str | None = None):
        return reference_readings(self.cfg, self.mix, self.seed,
                                  self.devices[:self.cell["chips"]], prec,
                                  fault)

    def _program(self) -> dict:
        return {"losses": self.losses, "grad_norms": self.grad_norms,
                "change_norms": self.change_norms}

    def check(self) -> dict:
        return compare(self._program(), self._reference())

    def control_readings(self, seconds: float) -> dict:
        """A sound run's numbers; the fp8 control's, and those of the faults
        a training cell can have, planted in the reference put in the
        program's place (a state left unchanged reads 1 on ``update_gap``
        by definition and needs no run).  Training needs no window."""
        self.setup()
        self.release()
        ref = self._reference()
        out = {"program": compare(self._program(), ref),
               "control": compare(self._reference("fp8"), ref)}
        faults = ["half_batch"] + (["no_exchange"] if self.cell["chips"] > 1
                                   else [])
        for f in faults:
            out[f] = compare(self._reference(fault=f), ref)
        return out


def planted(toks: np.ndarray, fault: str | None, chips: int) -> np.ndarray:
    """A batch as a faulty step would see it: ``half_batch`` keeps the
    first half of the rows (twice, so the mean is over them alone);
    ``no_exchange`` keeps the first chip's rows (as if no gradient came
    from the others)."""
    if fault is None:
        return toks
    keep = {"half_batch": toks.shape[0] // 2,
            "no_exchange": toks.shape[0] // chips}[fault]
    return np.concatenate([toks[:keep]] * (toks.shape[0] // keep))


def reference_readings(cfg: dict, mix: dict, seed: int, devices,
                       prec: str = "float32", fault: str | None = None
                       ) -> dict:
    """The plain reference's losses, first clipped gradient norms and
    changes, over ``mix["checked_steps"]`` steps from the seed's weights,
    its batch rows spread over ``devices``; ``fault`` plants one of
    ``planted``'s faults in the reference."""
    from bench.reference import load_reference

    ref_mod = load_reference(cfg)
    mesh = jax.sharding.Mesh(np.asarray(devices), ("rows",))
    rows = NamedSharding(mesh, P("rows"))
    rep = NamedSharding(mesh, P())
    m = weights.dims(cfg)
    tr = ref_mod.TrainReference(weights.make_params(cfg, seed, sharding=rep),
                                m, mix["optim"], prec, rows=rows,
                                replicated=rep)
    out = {"losses": [], "grad_norms": None}
    for k in range(mix["checked_steps"]):
        toks, labels = gen.train_batch(mix, cfg["vocab_size"], seed, k)
        toks, labels = (planted(x, fault, len(devices)) for x in (toks, labels))
        r = tr.step(jnp.asarray(toks), jnp.asarray(labels))
        out["losses"].append(r["loss"])
        if k == 0:
            out["grad_norms"] = r["leaf_grad_norms"]
    tr.mu = tr.nu = None         # the initial weights only now, in their room
    p0 = weights.make_params(cfg, seed, sharding=rep)
    out["change_norms"] = np.asarray(_diff_norms(tr.p, p0)).tolist()
    return out

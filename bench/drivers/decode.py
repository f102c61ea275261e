"""Serving window: the program's ``PagedDecodeEngine`` under continuous
batching with greedy decoding.

The loop mirrors ``ServeScheduler``'s continuous policy: before every step
each free slot takes the next request of the backlog (``admit``); every
live slot is fed one token (its prompt, one position a step, then its last
generated token); the step's logits are waited on (the greedy token is
taken over the published vocabulary on the device); a finished request is
retired at once.  The backlog never runs dry, so slots refill as soon as
they free up.

The check reruns the plain reference over a sample of the requests the
window served (finished, or still in flight at its close; drawn from the
seed, the longest among them) and reads, at every position served so far,
how far the served token's reference logit lies below the reference's
best.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops, gen, weights
from bench.drivers import common


class Driver(common.Driver):
    step_span = "decode"

    def setup(self):
        from repro.serve.engine import PagedDecodeEngine
        from repro.serve.kv import kv_page_payload_elems, plan_kv_arena

        cfg, mix = self.cfg, self.mix
        self.mesh = jax.make_mesh((1, 1), ("data", "model"),
                                  axis_types=(jax.sharding.AxisType.Auto,) * 2,
                                  devices=self.devices[:1])
        model = common.program_model(cfg)
        weights.check_layout(cfg, model.abstract_params())
        page_bytes = kv_page_payload_elems(model.cfg, mix["page_tokens"]) * 2
        plan = plan_kv_arena(model.cfg, self.mesh,
                             page_tokens=mix["page_tokens"],
                             page_bytes=page_bytes, max_seqs=mix["slots"],
                             max_seq_len=gen.max_context(mix))
        self.params = weights.make_params(
            cfg, self.seed,
            sharding=jax.sharding.SingleDeviceSharding(self.devices[0]))
        self.engine = PagedDecodeEngine(model, self.mesh, plan,
                                        attn_impl="kernel")
        vocab = cfg["vocab_size"]
        self.greedy = jax.jit(
            lambda lg: jnp.argmax(lg[:, :vocab], axis=-1).astype(jnp.int32))
        self.backlog = gen.Backlog(mix, vocab, self.seed)
        s = mix["slots"]
        self.slot_req = np.full((s,), -1, np.int64)
        self.fed = np.zeros((s,), np.int64)
        self.last = np.zeros((s,), np.int32)
        self.t_last = np.full((s,), np.nan)
        self.prompts: dict[int, np.ndarray] = {}
        self.served: dict[int, list[int]] = {}
        self.next_req = 0
        self.window_reqs: list[int] = []
        self.rec = None
        for _ in range(mix["warm_steps"]):
            self.step()

    def _admit(self):
        eng, bl = self.engine, self.backlog
        for slot in eng.free_slots():
            if self.next_req >= len(bl):
                raise RuntimeError("the backlog ran dry; make it longer")
            rid = self.next_req
            eng.admit(slot)
            self.slot_req[slot] = rid
            self.fed[slot] = 0
            self.t_last[slot] = np.nan
            self.prompts[rid] = bl.prompt(rid)
            self.served[rid] = []
            self.next_req += 1

    def step(self):
        eng, rec = self.engine, self.rec
        with jax.profiler.TraceAnnotation("bench.decode"):
            with jax.profiler.TraceAnnotation("bench.admit"):
                self._admit()
            live = np.nonzero(eng.slot_valid)[0]
            token = np.zeros((len(self.fed),), np.int32)
            for s in live:
                rid, k = self.slot_req[s], self.fed[s]
                p = self.prompts[rid]
                token[s] = p[k] if k < len(p) else self.last[s]
            logits = eng.decode(self.params, token)
            with jax.profiler.TraceAnnotation("bench.wait"):
                nxt = np.asarray(self.greedy(logits))
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.retire"):
                context = 0
                for s in live:
                    rid = self.slot_req[s]
                    self.fed[s] += 1
                    context += int(self.fed[s])
                    if self.fed[s] < len(self.prompts[rid]):
                        continue
                    tok = int(nxt[s])
                    out = self.served[rid]
                    if self.fault == "altered_token" and len(out) == ALTERED_AT:
                        tok = (tok + 1) % self.cfg["vocab_size"]
                    out.append(tok)
                    self.last[s] = tok
                    if rec is not None:
                        rec["tokens"] += 1
                        rec["requests"][int(rid)] = None
                        if not np.isnan(self.t_last[s]):
                            rec["gaps"].append(t - self.t_last[s])
                    self.t_last[s] = t
                    if len(out) == self.backlog.output_len[rid]:
                        eng.retire(int(s))
                        self.slot_req[s] = -1
                if rec is not None:
                    rec["need"].append((len(live), context))
        return t

    def _record(self):
        return {"tokens": 0, "gaps": [], "requests": {}, "need": []}

    def window(self, seconds: float) -> dict:
        self.rec = rec = self._record()
        t0 = time.perf_counter()
        while True:
            t1 = self.step()
            if t1 - t0 >= seconds:
                break
        self.rec = None
        self.window_reqs = list(rec["requests"])
        gaps = np.asarray(rec["gaps"], np.float64)
        tbt = float(np.percentile(gaps, 95)) * 1e3 if gaps.size else float("nan")
        return {"metrics": {"serve_tokens_per_s": rec["tokens"] / (t1 - t0),
                            "tbt_p95_ms": tbt},
                "attempted": len(self.window_reqs), "failed": 0}

    def traced_steps(self):
        self.trace_rec = self.rec = self._record()
        t0 = time.perf_counter()
        while self.step() - t0 < self.mix["trace_seconds"]:
            pass
        self.rec = None

    def layer_inputs(self, red: dict) -> dict:
        """Needed work of each traced decode step (every ``bench.decode``
        span in the trace is one of these)."""
        return {"need": [flops.decode_step_need(self.cfg, live, ctx)
                         for live, ctx in self.trace_rec["need"]]}

    def release(self):
        self.engine = None
        self.params = None
        super().release()

    def sample(self) -> list[int]:
        """Requests to check, among those the window served: the one with
        the most served tokens and, drawn from the seed,
        ``check_requests - 1`` others."""
        reqs = self.window_reqs
        if not reqs:
            return []
        longest = max(reqs, key=lambda r: len(self.served[r]))
        rest = [r for r in reqs if r != longest]
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, 7]))
        k = min(self.mix["check_requests"] - 1, len(rest))
        pick = rng.choice(len(rest), size=k, replace=False) if k else []
        return [longest] + [rest[i] for i in sorted(pick)]

    def _gaps(self, control: bool = False, altered: bool = False):
        """``reference_gaps`` over the sampled requests, and how many
        served tokens they hold."""
        streams = [(self.prompts[r], self.served[r]) for r in self.sample()]
        if not streams:
            return (float("inf"),) * 3, 0
        gaps = reference_gaps(self.cfg, self.seed, streams,
                              gen.max_context(self.mix), self.devices[0],
                              control=control, altered=altered)
        return gaps, sum(len(s) for _, s in streams)

    def check(self) -> dict:
        (gap, _, _), n = self._gaps()
        return {"logit_gap": gap, "_checked_tokens": n,
                "_checked_requests": len(self.sample())}

    def control_readings(self, seconds: float) -> dict:
        """A sound run's ``logit_gap``; the fp8 control's and an altered
        token's at the same served positions."""
        self.setup()
        self.window(seconds)
        self.release()
        (gap, ctrl, altered), n = self._gaps(control=True, altered=True)
        return {"program": {"logit_gap": gap}, "control": {"logit_gap": ctrl},
                "altered_token": {"logit_gap": altered}, "checked_tokens": n}


ALTERED_AT = 7   # the served position a planted fault alters


def reference_gaps(cfg: dict, seed: int, streams, pad_len: int, device,
                   control: bool = False, altered: bool = False):
    """``(gap, control_gap, altered_gap)``.  ``gap``: the widest gap, over
    every served position of ``streams`` (prompt, served tokens), between
    the reference's best logit and the served token's.  With ``control``,
    the same for the token the fp8 control puts first at those positions;
    with ``altered``, for a token altered at position ``ALTERED_AT``."""
    from bench.reference import load_reference

    ref = load_reference(cfg)
    m = weights.dims(cfg)
    params = weights.make_params(
        cfg, seed, sharding=jax.sharding.SingleDeviceSharding(device))
    worst = 0.0
    worst_ctrl = 0.0 if control else None
    worst_alt = 0.0 if altered else None
    for prompt, served in streams:
        seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
        toks = np.zeros((pad_len,), np.int32)
        toks[:len(seq)] = seq
        lo, n = len(prompt) - 1, len(served)
        rows = np.asarray(ref.stream_logits(params, toks, m)[lo:lo + n])
        best = rows.max(axis=1)
        worst = max(worst, float(np.max(best - rows[np.arange(n), served])))
        if control:
            c = np.asarray(ref.stream_logits(params, toks, m, "fp8")[lo:lo + n])
            pick = c.argmax(axis=1)
            worst_ctrl = max(worst_ctrl,
                             float(np.max(best - rows[np.arange(n), pick])))
        if altered and n > ALTERED_AT:
            k = ALTERED_AT
            tok = (served[k] + 1) % cfg["vocab_size"]
            worst_alt = max(worst_alt, float(best[k] - rows[k, tok]))
    return worst, worst_ctrl, worst_alt

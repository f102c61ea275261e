"""Serving window of a decoder with multi-head latent attention and expert
layers (DeepSeek-V3's layers): the decode driver's loop, check and
readings (``bench.drivers.decode``) over the program's
``PagedDecodeEngine`` with latent pages, with this configuration's own
model, weights, needed work and reference.

The loop runs one step ahead of what it reads: each call dispatches a step
and then reads the tokens of the one before.  A served token goes back into
the next step on the device, and which slot finishes or takes a new request
follows from the lengths alone, so nothing the next step needs waits for
the last step's tokens to reach the host.  The host's bookkeeping (about a
quarter of a step when it waits for every step) then runs while the device
works, and the time between tokens reads the device's step, steadily.

In the traced slice only, the engine counts into an ``Obs``; the slice's
counter totals (compiles, latent pages read of those walked, expert
choices and those on held experts) go to standard error.
"""

from __future__ import annotations

import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops_mla_moe, gen, weights_mla_moe
from bench.drivers import decode

COUNTERS = ("compiles", "kv_blocks_read", "kv_blocks_total",
            "moe_assignments", "moe_held_assignments")


def program_model(cfg: dict):
    """The program's ``Model`` for a configuration file: the registry entry
    of ``program_arch`` with every size the file states, and the share of
    experts this chip holds."""
    from repro.configs import get_config
    from repro.models import build_model

    want = {"scoring_func": "sigmoid", "topk_method": "noaux_tc",
            "n_group": 1, "norm_topk_prob": True, "q_lora_rank": None}
    for k, v in want.items():
        if cfg[k] != v:
            raise ValueError(f"the program routes and attends as {want}; "
                             f"the configuration has {k}={cfg[k]!r}")
    base = get_config(cfg["program_arch"])
    attn = dataclasses.replace(
        base.attn, num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["qk_nope_head_dim"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], rope_theta=float(cfg["rope_theta"]))
    moe = dataclasses.replace(
        base.moe, num_experts=cfg["n_routed_experts"],
        top_k=cfg["num_experts_per_tok"],
        expert_ff=cfg["moe_intermediate_size"],
        shared_expert_ff=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        interleave_step=cfg["moe_layer_freq"], scoring="sigmoid",
        routed_scaling=cfg["routed_scaling_factor"],
        experts_held=cfg["num_experts_held"],
        first_expert=cfg["first_expert_held"])
    mc = base.with_(num_layers=cfg["num_hidden_layers"],
                    d_model=cfg["hidden_size"],
                    d_ff=cfg["intermediate_size"],
                    vocab_size=cfg["vocab_size"], attn=attn, moe=moe,
                    first_k_dense=cfg["first_k_dense_replace"],
                    tie_embeddings=cfg["tie_word_embeddings"],
                    norm_eps=cfg["rms_norm_eps"], act=cfg["hidden_act"],
                    param_dtype=cfg["precision"]["params"],
                    dtype=cfg["precision"]["compute"])
    return build_model(mc)


class Driver(decode.Driver):
    def setup(self):
        from repro.serve.engine import PagedDecodeEngine
        from repro.serve.kv import kv_page_payload_elems, plan_kv_arena

        cfg, mix = self.cfg, self.mix
        self.mesh = jax.make_mesh((1, 1), ("data", "model"),
                                  axis_types=(jax.sharding.AxisType.Auto,) * 2,
                                  devices=self.devices[:1])
        model = program_model(cfg)
        weights_mla_moe.check_layout(cfg, model.abstract_params())
        itemsize = jnp.dtype(cfg["precision"]["kv_cache"]).itemsize
        page_bytes = kv_page_payload_elems(model.cfg,
                                           mix["page_tokens"]) * itemsize
        plan = plan_kv_arena(model.cfg, self.mesh,
                             page_tokens=mix["page_tokens"],
                             page_bytes=page_bytes, max_seqs=mix["slots"],
                             max_seq_len=gen.max_context(mix),
                             cache_dtype=cfg["precision"]["kv_cache"])
        self.params = weights_mla_moe.make_params(
            cfg, self.seed,
            sharding=jax.sharding.SingleDeviceSharding(self.devices[0]))
        self.engine = PagedDecodeEngine(model, self.mesh, plan,
                                        attn_impl="kernel")
        vocab = cfg["vocab_size"]
        self.greedy = jax.jit(
            lambda lg: jnp.argmax(lg[:, :vocab], axis=-1).astype(jnp.int32))
        self.feed = jax.jit(lambda last, prompt, from_last:
                            jnp.where(from_last, last, prompt))
        self.backlog = gen.Backlog(mix, vocab, self.seed)
        s = mix["slots"]
        self.slot_req = np.full((s,), -1, np.int64)
        self.fed = np.zeros((s,), np.int64)
        self.last = jnp.zeros((s,), jnp.int32)   # on the device
        self.t_last: dict[int, float] = {}
        self.pending = None     # the step in flight: (its tokens, who gets them)
        self.prompts: dict[int, np.ndarray] = {}
        self.served: dict[int, list[int]] = {}
        self.next_req = 0
        self.window_reqs: list[int] = []
        self.rec = None
        for _ in range(mix["warm_steps"]):
            self.step()
        self.drain()

    def _admit(self):
        eng, bl = self.engine, self.backlog
        for slot in eng.free_slots():
            if self.next_req >= len(bl):
                raise RuntimeError("the backlog ran dry; make it longer")
            rid = self.next_req
            eng.admit(slot)
            self.slot_req[slot] = rid
            self.fed[slot] = 0
            self.prompts[rid] = bl.prompt(rid)
            self.served[rid] = []
            self.next_req += 1

    def step(self):
        """Dispatch one step, then read the tokens of the step before it;
        returns the time they reached the host (the time of the call where
        none was in flight)."""
        eng, rec = self.engine, self.rec
        with jax.profiler.TraceAnnotation("bench.decode"):
            with jax.profiler.TraceAnnotation("bench.admit"):
                self._admit()
            live = np.nonzero(eng.slot_valid)[0]
            prompt = np.zeros((len(self.fed),), np.int32)
            from_last = np.zeros((len(self.fed),), bool)
            gets, context = [], 0
            for s in live:
                rid, k = self.slot_req[s], self.fed[s]
                p = self.prompts[rid]
                if k < len(p):
                    prompt[s] = p[k]
                else:
                    from_last[s] = True
                self.fed[s] += 1
                context += int(self.fed[s])
                if self.fed[s] >= len(p):
                    gets.append((int(s), int(rid)))
            logits = eng.decode(self.params,
                                self.feed(self.last, prompt, from_last))
            self.last = self.greedy(logits)
            for s, rid in gets:   # the last token due: the slot is free
                if (self.fed[s] - len(self.prompts[rid]) + 1
                        == self.backlog.output_len[rid]):
                    eng.retire(s)
                    self.slot_req[s] = -1
            if rec is not None:
                rec["need"].append((len(live), context))
            done, self.pending = self.pending, (self.last, gets)
            return self._read(done)

    def drain(self):
        """Read the step in flight, if any."""
        done, self.pending = self.pending, None
        with jax.profiler.TraceAnnotation("bench.drain"):
            return self._read(done)

    def _read(self, done):
        if done is None:
            return time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.wait"):
            nxt = np.asarray(done[0])
        t = time.perf_counter()
        rec = self.rec
        with jax.profiler.TraceAnnotation("bench.retire"):
            for s, rid in done[1]:
                tok = int(nxt[s])
                out = self.served[rid]
                # the planted fault alters the token served; the device
                # goes on from its own
                if (self.fault == "altered_token"
                        and len(out) == decode.ALTERED_AT):
                    tok = (tok + 1) % self.cfg["vocab_size"]
                out.append(tok)
                if rec is not None:
                    rec["tokens"] += 1
                    rec["requests"][rid] = None
                    if rid in self.t_last:
                        rec["gaps"].append(t - self.t_last[rid])
                self.t_last[rid] = t
                if len(out) == self.backlog.output_len[rid]:
                    del self.t_last[rid]
        return t

    def window(self, seconds: float) -> dict:
        """The decode driver's window over this loop: it opens with no
        step in flight, and closes when the tokens of a step read past
        ``seconds``."""
        self.drain()
        return super().window(seconds)

    def release(self):
        self.drain()
        super().release()

    def traced_steps(self):
        from repro.obs import NULL_OBS, Obs, ObsConfig

        obs = Obs(ObsConfig(run_dir=None))
        self.drain()
        self.engine.obs = obs
        try:
            super().traced_steps()
            self.drain()
        finally:
            self.engine.obs = NULL_OBS
        totals = {k: obs.bus.counter_total(k) for k in COUNTERS}
        self.log("traced slice counters " + json.dumps(totals))
        obs.finish()

    def layer_inputs(self, red: dict) -> dict:
        """Needed work of each traced decode step, and the scope of each
        instruction of the compiled step."""
        return {"need": [flops_mla_moe.decode_step_need(self.cfg, live, ctx)
                         for live, ctx in self.trace_rec["need"]],
                "scopes": self.engine.op_scopes()}

    def _gaps(self, control: bool = False, altered: bool = False):
        """``reference_gaps`` over the sampled requests, and how many
        served tokens they hold."""
        streams = [(self.prompts[r], self.served[r]) for r in self.sample()]
        if not streams:
            inf = np.full((1,), np.inf)
            return {"program": inf, "control": inf, "altered": inf}, 0
        gaps = reference_gaps(self.cfg, self.seed, streams,
                              gen.max_context(self.mix), self.devices[0],
                              control=control, altered=altered)
        return gaps, sum(len(s) for _, s in streams)

    def check(self) -> dict:
        """``logit_gap``, the widest gap at any served position, and
        ``logit_gap_mean``, the mean over them.  A top-k expert choice
        that a rounding flips moves one position's logits by about one
        here, so the widest gap of a sound run comes near the fp8
        control's; the mean, over thousands of positions, keeps them
        apart."""
        g, n = self._gaps()
        return {"logit_gap": float(np.max(g["program"])),
                "logit_gap_mean": float(np.mean(g["program"])),
                "_checked_tokens": n, "_checked_requests": len(self.sample())}

    def control_readings(self, seconds: float) -> dict:
        """A sound run's numbers; the fp8 control's and an altered token's
        at the same served positions."""
        self.setup()
        self.window(seconds)
        self.release()
        g, n = self._gaps(control=True, altered=True)

        def nums(x):
            return {"logit_gap": float(np.max(x)),
                    "logit_gap_mean": float(np.mean(x))}
        return {"program": nums(g["program"]), "control": nums(g["control"]),
                "altered_token": nums(g["altered"]), "checked_tokens": n}


@jax.jit
def _best_and_picked(logits, rows, picks):
    """The best logit at each of ``rows`` and the logit of ``picks`` there,
    read on the device (the (T, vocab) logits stay there)."""
    return jnp.max(logits, axis=1)[rows], logits[rows, picks]


@jax.jit
def _argmax(logits):
    return jnp.argmax(logits, axis=1).astype(jnp.int32)


def reference_gaps(cfg: dict, seed: int, streams, pad_len: int, device,
                   control: bool = False, altered: bool = False) -> dict:
    """``bench.drivers.decode.reference_gaps`` with this configuration's
    weights and reference, position by position: ``{"program": gaps}``,
    at every served position of ``streams`` the gap between the
    reference's best logit and the served token's; with ``control``,
    ``"control"``, the same for the token the fp8 control puts first; with
    ``altered``, ``"altered"``, the program's gaps with the token at
    position ``ALTERED_AT`` of each stream altered."""
    from bench.reference import load_reference

    ref = load_reference(cfg)
    m = weights_mla_moe.dims(cfg)
    params = weights_mla_moe.make_params(
        cfg, seed, sharding=jax.sharding.SingleDeviceSharding(device))
    out: dict = {"program": [], "control": [], "altered": []}
    at = decode.ALTERED_AT
    for prompt, served in streams:
        seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
        toks = np.zeros((pad_len,), np.int32)
        toks[:len(seq)] = seq
        lo, n = len(prompt) - 1, len(served)
        rows = jnp.arange(lo, lo + n)
        if control:   # one (T, vocab) array on the device at a time
            pick = _argmax(ref.stream_logits(params, toks, m, "fp8"))[rows]
        lg = ref.stream_logits(params, toks, m)
        best, got = map(np.asarray, _best_and_picked(
            lg, rows, jnp.asarray(served, jnp.int32)))
        out["program"].append(best - got)
        if control:
            out["control"].append(best - np.asarray(
                _best_and_picked(lg, rows, pick)[1]))
        if altered:
            gaps = best - got
            if n > at:
                tok = jnp.asarray([(served[at] + 1) % cfg["vocab_size"]])
                a = _best_and_picked(lg, rows[at:at + 1], tok)[1]
                gaps[at] = best[at] - float(np.asarray(a)[0])
            out["altered"].append(gaps)
        del lg
    return {k: np.concatenate(v) for k, v in out.items() if v}

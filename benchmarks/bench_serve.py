"""Continuous batching A/B + paged-decode throughput sweep (repro.serve).

Part 1 — the acceptance-bar A/B: the mixed-length synthetic trace
(``repro.serve.scheduler.mixed_trace``) under the ``continuous`` vs
``static`` batching policies on one engine (no recompiles between runs).
Rows print as::

    policy,steps,generated,tok_per_step,tok_per_s,mean_live

followed by the two throughput ratios; ``ratio_tok_per_s`` is the paper's
claim (≥ 2x on the mixed trace — a long sequence no longer holds every
other slot hostage).

Part 2 — tokens/sec vs batch (slots) x page_tokens, with the serving
prediction layer's per-token collective count/wire bytes as columns
(asserted against lowered HLO at zero tolerance in the dry-run's
``--suite serve``; here they annotate measured throughput)::

    slots,page_tokens,model_parallel,coll_per_tok,wire_B_per_tok,kv_bytes,kv_pages,tok_per_s

On shared-memory host devices this measures the *mechanism* (one compiled
step, in-flight admit/retire, page recycling) — wire-level effects live in
the dry-run roofline (EXPERIMENTS.md explains the split).

``--dry`` runs a tiny trace + one sweep combo as a CI smoke.
"""

from __future__ import annotations

import argparse

from benchmarks.common import TIMER_SNIPPET, run_on_devices

SCRIPT = TIMER_SNIPPET + r"""
import time
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import AxisType
from repro.configs import reduced_config
from repro.models import build_model
from repro.serve.engine import (PagedDecodeEngine,
                                predicted_collectives_per_token,
                                predicted_wire_bytes_per_token)
from repro.serve.kv import plan_kv_arena
from repro.serve.scheduler import ServeScheduler, mixed_trace

DRY = %(dry)s
ARCH = "llama3.2-1b"
cfg = reduced_config(ARCH)
model = build_model(cfg)
params = model.init(jax.random.key(0))

def make_engine(slots, page_tokens, r, max_seq_len):
    mesh = jax.make_mesh((1, r), ("data", "model"),
                         devices=jax.devices()[:r],
                         axis_types=(AxisType.Auto,) * 2)
    plan = plan_kv_arena(cfg, mesh, page_tokens=page_tokens,
                         page_bytes=4096, max_seqs=slots,
                         max_seq_len=max_seq_len)
    return PagedDecodeEngine(model, mesh, plan, attn_impl="ref"), plan

# --- part 1: continuous vs static on the mixed-length trace ---------------
groups, slots, long_len, short_len = (3, 3, 8, 2) if DRY else (4, 4, 64, 4)
eng, plan = make_engine(slots, 8, 1, long_len + 1)
# compile the (one) step before timing either policy — fixed traced shapes
# mean both runs then reuse it.  Two warmup steps: the first compiles for
# the fresh arena buffer, the second for the steady state where the donated
# pages output (now carrying the mesh sharding) threads back in.
eng.admit(0)
for _ in range(2):
    jax.block_until_ready(eng.decode(params, np.zeros(slots, np.int32)))
eng.retire(0)
print("policy,steps,generated,tok_per_step,tok_per_s,mean_live")
res = {}
for policy in ("continuous", "static"):
    trace = mixed_trace(groups=groups, slots=slots, long_len=long_len,
                        short_len=short_len)
    sched = ServeScheduler(eng, policy)
    t0 = time.perf_counter()
    r = sched.run(params, trace)
    jax.block_until_ready(eng.pages)      # drain the async dispatch queue
    wall = time.perf_counter() - t0
    r["tok_per_s"] = r["generated_tokens"] / wall
    res[policy] = r
    print(f"{policy},{r['steps']},{r['generated_tokens']},"
          f"{r['tokens_per_step']:.3f},{r['tok_per_s']:.1f},"
          f"{r['mean_live_slots']:.2f}")
print(f"ratio_tok_per_s,{res['continuous']['tok_per_s'] / res['static']['tok_per_s']:.2f}")
print(f"ratio_tok_per_step,{res['continuous']['tokens_per_step'] / res['static']['tokens_per_step']:.2f}")

# --- part 2: tokens/sec vs slots x page_tokens (+ a model-parallel row) ---
combos = [(2, 8, 1)] if DRY else [(2, 8, 1), (2, 16, 1), (4, 8, 1),
                                  (4, 16, 1), (4, 16, 2)]
n_steps = 4 if DRY else 16
print("slots,page_tokens,model_parallel,coll_per_tok,wire_B_per_tok,"
      "kv_bytes,kv_pages,tok_per_s")
for slots, pt, r in combos:
    eng, plan = make_engine(slots, pt, r, n_steps + 2)
    for s in range(slots):
        eng.admit(s)
    token = np.arange(slots, dtype=np.int32)
    for _ in range(2):                # fresh-arena + steady-state compiles
        jax.block_until_ready(eng.decode(params, token))
    t0 = time.perf_counter()
    for _ in range(n_steps - 1):
        jax.block_until_ready(eng.decode(params, token))
    wall = time.perf_counter() - t0
    tps = slots * (n_steps - 1) / wall
    print(f"{slots},{pt},{r},{predicted_collectives_per_token(plan)},"
          f"{predicted_wire_bytes_per_token(plan, cfg, slots):.0f},"
          f"{plan.total_bytes},{plan.n_arena_pages},{tps:.1f}")
"""


def run(dry: bool = False) -> str:
    return run_on_devices(SCRIPT % {"dry": dry})


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--dry", action="store_true",
                    help="tiny trace + one sweep combo (CI smoke)")
    args = ap.parse_args()
    print(run(dry=args.dry))

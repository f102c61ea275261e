"""Serving driver: prefill+decode loop for an assigned architecture.

Two paths:

* default — the contiguous-cache decode loop over ``build_decode_step``
  (resident or gathered weights, production mesh optional)::

      PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
          --reduced --tokens 16

* ``--paged`` — the ``repro.serve`` stack: paged KV arena + continuous
  batching scheduler + flash-decode attention, driven over a mixed-length
  synthetic trace.  ``--policy both`` runs the continuous-vs-static A/B
  the paper-style acceptance bar measures::

      PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
          --reduced --paged --policy both
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import AxisType
from repro.configs import get_config, list_archs, reduced_config
from repro.configs.base import ShapeConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.launch.settings import settings_for
from repro.models import build_model
from repro.models.transformer import init_decode_state
from repro.runtime.serve_step import build_decode_step
from repro.sharding import shardings_of


def run_paged(args) -> None:
    from repro.serve.engine import (PagedDecodeEngine,
                                    predicted_collectives_per_token,
                                    predicted_wire_bytes_per_token)
    from repro.serve.kv import plan_kv_arena
    from repro.serve.scheduler import ServeScheduler, mixed_trace

    from repro.obs import ObsConfig, make_obs

    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    model = build_model(cfg)
    r = args.model_parallel
    if r > len(jax.devices()):
        raise SystemExit(f"--model-parallel {r} needs {r} devices, have "
                         f"{len(jax.devices())}")
    mesh = jax.make_mesh((1, r), ("data", "model"),
                         devices=jax.devices()[:r],
                         axis_types=(AxisType.Auto,) * 2)
    longest = args.prompt_len + max(args.long_len, args.short_len)
    plan = plan_kv_arena(cfg, mesh, page_tokens=args.page_tokens,
                         max_seqs=args.slots, max_seq_len=longest)
    obs = make_obs(ObsConfig(run_dir=args.obs_dir)
                   if args.obs_dir else None)
    engine = PagedDecodeEngine(model, mesh, plan, attn_impl=args.attn_impl,
                               obs=obs)
    params = model.init(jax.random.key(0))
    trace = mixed_trace(groups=args.groups, slots=args.slots,
                        long_len=args.long_len, short_len=args.short_len,
                        prompt_len=args.prompt_len)
    print(f"{args.arch}: paged serve, {len(trace)} requests, "
          f"{plan.n_kv_pages} KV pages ({plan.total_bytes} B arena), "
          f"page_tokens={plan.page_tokens}, R={r} "
          f"({predicted_collectives_per_token(plan)} collectives/token, "
          f"{predicted_wire_bytes_per_token(plan, cfg, plan.max_seqs):.0f} "
          f"wire B/token)")
    policies = (["continuous", "static"] if args.policy == "both"
                else [args.policy])
    results = {}
    for policy in policies:
        sched = ServeScheduler(engine, policy)
        t0 = time.time()
        res = sched.run(params, list(trace))
        res["wall_s"] = time.time() - t0
        res["tokens_per_s"] = res["generated_tokens"] / res["wall_s"]
        results[policy] = res
        print(f"  {policy:10s}: {res['steps']} steps, "
              f"{res['generated_tokens']} tokens, "
              f"{res['tokens_per_step']:.3f} tok/step, "
              f"{res['tokens_per_s']:.1f} tok/s, "
              f"mean live slots {res['mean_live_slots']:.2f}")
    if len(results) == 2:
        ratio = (results["continuous"]["tokens_per_step"]
                 / results["static"]["tokens_per_step"])
        print(f"  continuous / static throughput: {ratio:.2f}x")
    paths = obs.finish()
    if paths and paths.get("events"):
        print(f"  obs: events={paths['events']} trace={paths['trace']}")


def run_contiguous(args) -> None:
    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    if cfg.family == "encdec":
        raise SystemExit("enc-dec serving demo: use examples/serve_lm.py "
                         "patterns with encdec.init_decode_state")
    model = build_model(cfg)
    mesh = (make_production_mesh() if args.production_mesh
            else make_host_mesh())
    shape = ShapeConfig("serve", args.cache, args.batch, "decode")
    wm = settings_for(args.arch).serve_weights if not args.reduced else "resident"
    step, pspecs, sspecs = build_decode_step(model, mesh, shape,
                                             weight_mode=wm)
    params = model.init(jax.random.key(0))
    with mesh:
        params = jax.jit(lambda p: p,
                         out_shardings=shardings_of(pspecs, mesh))(params)
        state = init_decode_state(model.cfg, args.batch, args.cache)
        state = jax.jit(lambda s: s,
                        out_shardings=shardings_of(sspecs, mesh))(state)
    token = jnp.zeros((args.batch,), jnp.int32)
    t0 = time.time()
    for pos in range(args.tokens):
        with mesh:
            logits, state = step(params, token, state, jnp.asarray(pos))
        token = jnp.clip(jnp.argmax(logits, -1).astype(jnp.int32), 0,
                         model.cfg.vocab_size - 1)
    dt = time.time() - t0
    print(f"{args.arch}: {args.tokens * args.batch / dt:.1f} tok/s "
          f"(batch {args.batch}, cache {args.cache})")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--cache", type=int, default=512)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--paged", action="store_true",
                    help="serve through the repro.serve paged KV engine + "
                         "continuous batching scheduler instead of the "
                         "contiguous-cache loop")
    ap.add_argument("--policy", default="continuous",
                    choices=["continuous", "static", "both"],
                    help="paged: batching policy ('both' prints the A/B "
                         "throughput ratio)")
    ap.add_argument("--attn-impl", default="kernel",
                    choices=["kernel", "ref"],
                    help="paged: score pages with the Pallas flash-decode "
                         "kernel or the jnp oracle")
    ap.add_argument("--page-tokens", type=int, default=16,
                    help="paged: token positions per KV page")
    ap.add_argument("--slots", type=int, default=4,
                    help="paged: concurrent sequence slots")
    ap.add_argument("--model-parallel", type=int, default=1,
                    help="paged: model-axis size (page-parallel decode + "
                         "LSE all-reduce)")
    ap.add_argument("--groups", type=int, default=4,
                    help="paged: mixed-trace groups (1 long + slots-1 "
                         "short requests each)")
    ap.add_argument("--long-len", type=int, default=64)
    ap.add_argument("--short-len", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=1)
    ap.add_argument("--obs-dir", default=None, metavar="DIR",
                    help="paged: instrument the run (JSONL events + Chrome "
                         "trace under DIR)")
    args = ap.parse_args()
    enable_compile_cache()

    if args.paged:
        run_paged(args)
    else:
        run_contiguous(args)


if __name__ == "__main__":
    main()

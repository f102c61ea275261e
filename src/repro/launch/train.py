"""Production train driver: ``--arch <id>`` selects an assigned architecture.

On real hardware this runs under the cluster launcher (one process per
host); on this container it runs reduced configs on host devices:

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
        --reduced --steps 50
"""

from __future__ import annotations

import argparse
import dataclasses

import jax

from repro.comm import CommConfig, SCHEDULE_POLICIES, list_transports
from repro.configs import get_config, list_archs, reduced_config
from repro.configs.base import ShapeConfig
from repro.data import DataConfig, SyntheticTokens
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.launch.settings import settings_for
from repro.obs import ObsConfig
from repro.tune import resolve
from repro.models import build_model
from repro.optim import OptimConfig
from repro.runtime.train_loop import Trainer, TrainerConfig
from repro.runtime.train_step import DP_MODES, TrainStepConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale config (host execution)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--transport", default=None, choices=list_transports(),
                    help="repro.comm transport (default: the arch's setting)")
    ap.add_argument("--channels", type=int, default=None,
                    help="virtual comm rails (0 = unconstrained)")
    ap.add_argument("--dp-mode", default=None, choices=DP_MODES)
    ap.add_argument("--accum-policy", default=None, choices=SCHEDULE_POLICIES,
                    help="gradient-reduction issue schedule (default: "
                         "accumulate_then_reduce)")
    ap.add_argument("--use-arena", action="store_true",
                    help="reduce out of the page-aligned repro.mem "
                         "CommArena (fused spans, donated buffer)")
    ap.add_argument("--page-bytes", type=int, default=None,
                    help="arena page size (default 2 MiB)")
    ap.add_argument("--wire-codec", default=None, choices=["int8"],
                    help="quantize the gradient wire (int8 payload + "
                         "per-block scales, error feedback; with "
                         "--use-arena the fused pack+quantize path)")
    ap.add_argument("--production-mesh", action="store_true",
                    help="use the 16x16 mesh (needs 256 devices)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--tuned", default=None, metavar="DB",
                    help="tuning DB (repro.tune.probe output): resolve the "
                         "arch's 'auto' comm knobs — and any channels=0 — "
                         "to the DB's measured-best config before launch")
    ap.add_argument("--obs-dir", default=None, metavar="DIR",
                    help="instrument the run: JSONL event stream + Chrome "
                         "trace under DIR (read with "
                         "python -m repro.obs.report DIR)")
    ap.add_argument("--obs-predict", action="store_true",
                    help="AOT-price the step (roofline; with --tuned, the "
                         "DB's measured alpha/beta) and track live "
                         "predicted-vs-measured drift")
    args = ap.parse_args()
    enable_compile_cache()

    st = settings_for(args.arch)
    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    model = build_model(cfg)
    mesh = (make_production_mesh(multi_pod=args.multi_pod)
            if args.production_mesh else make_host_mesh())
    mesh_label = "x".join(str(d) for d in mesh.devices.shape)
    if args.tuned or resolve.has_auto(st):
        st, info = resolve.resolve_settings(st, args.arch,
                                            mesh_label=mesh_label,
                                            db_path=args.tuned)
        if info["source"] == "db":
            print(f"tuned: {info['key']} "
                  f"(alpha={info['alpha_s']*1e6:.2f}us "
                  f"bw={info['bandwidth']/1e9:.2f}GB/s) -> "
                  f"transport={st.transport} channels={st.channels} "
                  f"page_bytes={st.page_bytes}")
    print(f"arch={args.arch} params={model.param_count()/1e6:.1f}M "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}")

    schedule = "wsd" if args.arch == "minicpm-2b" else "cosine"
    shape = ShapeConfig("train", args.seq, args.batch, "train")
    data = SyntheticTokens(DataConfig(vocab_size=model.cfg.vocab_size,
                                      seq_len=args.seq,
                                      global_batch=args.batch),
                           model_cfg=cfg)
    ccfg = st.comm_config(bucket_bytes=32 * 2**20)
    if args.transport:
        ccfg = dataclasses.replace(ccfg, transport=args.transport)
    if args.channels is not None:
        ccfg = dataclasses.replace(ccfg, channels=args.channels)
    if args.page_bytes is not None:
        ccfg = dataclasses.replace(ccfg, page_bytes=args.page_bytes)
    step_cfg = TrainStepConfig(
        dp_mode=args.dp_mode or (st.dp_mode if not args.reduced else "replicated"),
        comm=ccfg,
        optim=OptimConfig(base_lr=args.lr, warmup=min(20, args.steps // 5),
                          schedule=schedule, total_steps=args.steps),
        microbatches=1 if args.reduced else st.microbatches,
        schedule=args.accum_policy or "accumulate_then_reduce",
        use_arena=args.use_arena, wire_codec=args.wire_codec,
        moe_transport=st.moe_transport, moe_channels=st.moe_channels)
    obs_cfg = None
    if args.obs_dir or args.obs_predict:
        obs_cfg = ObsConfig(run_dir=args.obs_dir,
                            predict=args.obs_predict,
                            tuned_db=args.tuned if args.obs_predict else None)
    trainer = Trainer(model, mesh, step_cfg, data, shape,
                      TrainerConfig(steps=args.steps, ckpt_every=50,
                                    ckpt_dir=args.ckpt_dir, log_every=10,
                                    obs=obs_cfg))
    out = trainer.run()
    if obs_cfg is not None and out.get("obs", {}).get("events"):
        print(f"obs: events={out['obs']['events']} "
              f"trace={out['obs']['trace']}")


if __name__ == "__main__":
    main()

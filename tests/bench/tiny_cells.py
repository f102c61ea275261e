"""Tiny stand-ins of the benchmark's cells for CPU tests: the same
drivers, references and checks, at widths a test run can hold.

Besides the cells of ``BENCHMARK.json``, the training cells held back from
it (the benchmark keeps their driver, configuration and mixes) are run
here, with limits of their own."""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402

TINY = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
        "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 500}
# training at a size the program runs unpadded: 16 query heads (a multiple
# of its head padding) and a vocabulary that is a multiple of 128
TINY_TRAIN = dict(TINY, num_attention_heads=16, num_key_value_heads=16,
                  head_dim=4, vocab_size=512)

TRAIN = {"cfg": TINY_TRAIN,
         "mix": {"seq_len": 64, "global_batch": 2, "trace_steps": 2}}
DP4 = {"cfg": TINY_TRAIN,
       "mix": {"seq_len": 64, "global_batch": 8, "trace_steps": 2}}
SERVE = {"cfg": dict(TINY, num_key_value_heads=2),
         "mix": {"slots": 4, "page_tokens": 8, "backlog": 64, "warm_steps": 4,
                 "trace_seconds": 0.5,
                 "prompt": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                            "min": 4, "max": 16},
                 "output": {"dist": "lognormal", "median": 16, "sigma": 0.5,
                            "min": 8, "max": 32}}}

CELLS = {"minicpm-2b.train.1chip": TRAIN, "minicpm-2b.train.dp4": DP4,
         "phi3-medium-14b.serve.decode_heavy": SERVE}

_TRAIN_LAYER = [{"name": "mfu.train", "unit": "%"},
                {"name": "idle_share.train", "unit": "%"}]
_COLLECTIVES = [{"name": "collective_ms.train", "unit": "ms"},
                {"name": "collective_exposed_ms.train", "unit": "ms"}]
# held-back cells: (traffic, chips, per-layer metrics, limits)
HELD_BACK = {
    "minicpm-2b.train.1chip": (
        "train.s2048.b2", 1, _TRAIN_LAYER,
        {"loss_gap": 0.03, "grad_gap": 0.2, "update_gap": 0.0025}),
    "minicpm-2b.train.dp4": (
        "train.s2048.b8", 4, _TRAIN_LAYER + _COLLECTIVES,
        {"loss_gap": 0.035, "grad_gap": 0.2, "update_gap": 0.008}),
}

SEED = 2**31 + 4242


def _load(*parts):
    with open(os.path.join(ROOT, "bench", *parts)) as f:
        return json.load(f)


def parts(workload, overrides=None):
    """``harness.cell_parts`` of a tiny cell: the cell's files with the
    tiny sizes laid over them."""
    o = overrides or CELLS[workload]
    if workload in HELD_BACK:
        traffic, chips, per_layer, limits = HELD_BACK[workload]
        cell = {"name": workload, "config": "minicpm-2b", "traffic": traffic,
                "chips": chips}
        cfg, mix = _load("configs", "minicpm-2b.json"), _load(
            "traffic", traffic + ".json")
        e2e = [{"name": "setup_s", "unit": "s"},
               {"name": "train_tokens_per_s", "unit": "tokens/s"}]
    else:
        cell, cfg, mix, limits, e2e, per_layer = harness.cell_parts(workload)
    return (cell, dict(cfg, **o.get("cfg", {})), dict(mix, **o.get("mix", {})),
            limits, e2e, per_layer)


def run(workload, *, seconds=0.5, traced=False, fault=None, seed=SEED,
        overrides=None):
    """One run of a tiny cell on the CPU (the look for a chip skipped)."""
    return harness.run_cell(workload, seed, seconds, traced,
                            t_start=time.time(), require_chip=False,
                            parts=parts(workload, overrides), fault=fault,
                            log=lambda *a: None)

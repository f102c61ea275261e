"""A configuration, a traffic mix, a driver, a reference and a per-layer
metric added as new files (plus entries in BENCHMARK.json) are found by
name, with no existing file of the benchmark edited: a copy of the
benchmark gains one of each, and a tiny run of the new cell reports the
new metric."""

import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

NEW_FILES = {
    "configs/tiny-lm.json": None,   # filled from minicpm-2b below
    "traffic/train.s64.b2.json": None,
    "limits/tiny-lm.train.json": {"loss_gap": 0.05, "grad_gap": 0.1,
                                  "update_gap": 0.1},
    "drivers/train_again.py": "from bench.drivers.train import Driver  # noqa: F401\n",
    "reference/dense_again.py": "from bench.reference.dense_decoder import *  # noqa: F401,F403\n",
    "metrics/steps_traced.py": ('def read(r):\n'
                                '    return float(r["trace"].get("steps", 0)) or None\n'),
}

SCRIPT = """
import json, sys, time
sys.path[:0] = [{src!r}, {root!r}]
from bench import harness
out = harness.run_cell("tiny-lm.train", 2**31 + 9, 0.3, True,
                       t_start=time.time(), require_chip=False,
                       log=lambda *a: None)
print(json.dumps(out))
"""


def _load(*p):
    with open(os.path.join(*p)) as f:
        return json.load(f)


def test_new_cell_found_by_name(tmp_path):
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {os.path.join(dp, p): open(os.path.join(dp, p), "rb").read()
              for dp, _, fs in os.walk(root / "bench") for p in fs}
    cfg = _load(ROOT, "bench", "configs", "minicpm-2b.json")
    cfg.update(name="tiny-lm", reference="dense_again", hidden_size=64,
               intermediate_size=128, num_attention_heads=16,
               num_key_value_heads=16, head_dim=4, num_hidden_layers=2,
               vocab_size=512)
    mix = _load(ROOT, "bench", "traffic", "train.s2048.b2.json")
    mix.update(driver="train_again", seq_len=64, trace_steps=2)
    files = dict(NEW_FILES)
    files["configs/tiny-lm.json"] = cfg
    files["traffic/train.s64.b2.json"] = mix
    for rel, body in files.items():
        path = root / "bench" / rel
        assert not path.exists()
        path.write_text(body if isinstance(body, str) else json.dumps(body))
    spec = _load(ROOT, "BENCHMARK.json")
    spec["configs"].append({"name": "tiny-lm", "source": "test",
                            "file": "bench/configs/tiny-lm.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny-lm.train", "config": "tiny-lm",
                              "traffic": "train.s64.b2", "chips": 1,
                              "why": "test"})
    spec["end_to_end"].append({"name": "train_tokens_per_s",
                               "unit": "tokens/s", "better": "higher",
                               "bound": 0.01, "source": "host_clock",
                               "workloads": ["tiny-lm.train"]})
    spec["per_layer"].append({"name": "steps_traced", "unit": "steps",
                              "better": "higher", "source": "device_trace",
                              "layer": "device", "moves": "train_tokens_per_s",
                              "workloads": ["tiny-lm.train"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    script = SCRIPT.format(src=os.path.join(ROOT, "src"), root=str(root))
    proc = subprocess.run([sys.executable, "-c", script], cwd=root, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"], out["checks"]
    assert out["metrics"]["steps_traced"]["value"] == 2.0
    for path, body in before.items():   # no file it had was edited
        with open(path, "rb") as f:
            assert f.read() == body, path

"""FLOP and byte counters (bench/flops.py), tied to the program's
parameter tree at each configuration's published sizes, and the table of
peaks."""

import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402
import pytest  # noqa: E402

from bench import flops, peaks, weights  # noqa: E402
from bench.drivers.common import program_model  # noqa: E402

CONFIGS = ["minicpm-2b", "phi3-medium-14b"]


def _cfg(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


def _published_matmul_params(cfg, abstract):
    """Matmul weights of the program's tree with its padding cut away:
    query heads beyond the published count, vocabulary rows beyond the
    published vocabulary."""
    live = cfg["num_attention_heads"] * cfg["head_dim"]
    vocab = cfg["vocab_size"]
    total = 0
    for blk in abstract["blocks"]:
        a = blk["attn"]
        total += a["wq"]["w"].shape[0] * live + a["wo"]["w"].shape[1] * live
        total += math.prod(a["wk"]["w"].shape) + math.prod(a["wv"]["w"].shape)
        total += sum(math.prod(blk["mlp"][k]["w"].shape)
                     for k in ("w_gate", "w_up", "w_down"))
    head = (abstract["embed"]["table"] if cfg["tie_word_embeddings"]
            else abstract["lm_head"]["w"])
    total += min(head.shape) * vocab
    return total


@pytest.mark.parametrize("name", CONFIGS)
def test_matmul_params_match_the_program_tree(name):
    cfg = _cfg(name)
    abstract = program_model(cfg).abstract_params()
    weights.check_layout(cfg, abstract)
    assert flops.matmul_params(cfg) == _published_matmul_params(cfg, abstract)
    # the program pads: heads to a multiple of 16, vocab to one of 128
    wq = abstract["blocks"][0]["attn"]["wq"]["w"]
    assert wq.shape[1] == weights.pad_to(cfg["num_attention_heads"],
                                         16) * cfg["head_dim"]
    assert abstract["embed"]["table"].shape[0] == weights.pad_to(
        cfg["vocab_size"], 128)


def test_train_step_flops_minicpm():
    cfg = _cfg("minicpm-2b")
    mix = {"global_batch": 2, "seq_len": 2048}
    n = flops.matmul_params(cfg)
    layer = 4 * 2304 * 2304 + 3 * 2304 * 5760
    assert n == cfg["num_hidden_layers"] * layer + 2304 * 122753
    attn = 12 * (2048 ** 2 / 2) * 36 * 64 * 2 * cfg["num_hidden_layers"]
    assert flops.train_step_flops(cfg, mix) == pytest.approx(
        6 * n * 4096 + attn)


def test_decode_step_need_phi3():
    cfg = _cfg("phi3-medium-14b")
    n = flops.matmul_params(cfg)
    kv_tok = 2 * 10 * 128 * 2 * cfg["num_hidden_layers"]
    assert flops.kv_bytes_per_token(cfg) == kv_tok
    need = flops.decode_step_need(cfg, live=3, context=300)
    assert need["bytes"] == 4 * n + 300 * kv_tok
    assert need["flops"] == pytest.approx(
        2 * n * 3 + 4 * 40 * 128 * 300 * cfg["num_hidden_layers"])
    assert need["attn_bytes"] == 300 * kv_tok + 3 * cfg["num_hidden_layers"] \
        * 40 * 128 * 6
    # weights dominate a decode step's bytes at these lengths
    assert need["bytes"] / 819e9 > need["flops"] / 197e12


def test_peaks_table():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


def test_weights_are_seeded_and_padded():
    cfg = dict(_cfg("phi3-medium-14b"), hidden_size=64, intermediate_size=96,
               head_dim=16, num_attention_heads=4, num_key_value_heads=2,
               num_hidden_layers=1, vocab_size=300)
    a = weights.make_params(cfg, 2**33 + 1)
    b = weights.make_params(cfg, 2**33 + 1)
    c = weights.make_params(cfg, 1)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert (x == y).all()
    assert not (a["embed"]["table"] == c["embed"]["table"]).all()
    wo = a["blocks"][0]["attn"]["wo"]["w"]
    assert wo.shape == (16 * 16, 64)
    assert (wo[4 * 16:] == 0).all() and (wo[:4 * 16] != 0).any()
    weights.check_layout(cfg, program_model(cfg).abstract_params())

"""The traffic generator (bench/gen.py): every mix is seeded and
reproducible, and a serving backlog offers every seed the same work."""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import gen  # noqa: E402

BIG = 2**31 + 987654321   # seeds beyond 32 signed bits


def _mix(name):
    with open(os.path.join(ROOT, "bench", "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["train.s2048.b2", "train.s2048.b8"])
def test_train_batches_are_seeded_and_reproducible(name):
    mix = _mix(name)
    a = gen.train_batch(mix, 122753, BIG, 3)
    b = gen.train_batch(mix, 122753, BIG, 3)
    c = gen.train_batch(mix, 122753, BIG + 1, 3)
    d = gen.train_batch(mix, 122753, BIG, 4)
    shape = (mix["global_batch"], mix["seq_len"])
    assert a[0].shape == a[1].shape == shape and a[0].dtype == np.int32
    np.testing.assert_array_equal(a[0], b[0])
    assert not np.array_equal(a[0], c[0]) and not np.array_equal(a[0], d[0])
    np.testing.assert_array_equal(a[0][:, 1:], a[1][:, :-1])  # next tokens
    assert a[0].min() >= 0 and a[0].max() < 122753
    rows = {r.tobytes() for r in a[0]}
    assert len(rows) == shape[0]                               # rows differ


def test_backlog_same_work_for_every_seed():
    mix = _mix("decode_heavy")
    a, b = gen.Backlog(mix, 32064, BIG), gen.Backlog(mix, 32064, 7)
    assert len(a) == mix["backlog"]
    np.testing.assert_array_equal(a.prompt_len, b.prompt_len)
    np.testing.assert_array_equal(a.output_len, b.output_len)
    assert a.prompt_len.min() >= mix["prompt"]["min"]
    assert a.output_len.max() <= mix["output"]["max"]
    assert (a.prompt_len + a.output_len).max() <= gen.max_context(mix)
    med = np.median(a.output_len)
    assert 0.8 * mix["output"]["median"] < med < 1.2 * mix["output"]["median"]


def test_backlog_prompts_are_seeded():
    mix = _mix("decode_heavy")
    a, a2 = gen.Backlog(mix, 32064, BIG), gen.Backlog(mix, 32064, BIG)
    b = gen.Backlog(mix, 32064, BIG + 1)
    np.testing.assert_array_equal(a.prompt(5), a2.prompt(5))
    assert len(a.prompt(5)) == a.prompt_len[5]
    assert not np.array_equal(a.prompt(5), b.prompt(5))
    assert a.prompt(5).max() < 32064

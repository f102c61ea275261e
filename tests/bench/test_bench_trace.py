"""The trace reduction (bench/trace.py): on hand-made events with known
answers, and on a small trace recorded on the CPU (two host devices, a
matmul and a collective-permute per step, a host sleep inside a
``bench.data`` span)."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

from bench import trace  # noqa: E402

RECORDED = os.path.join(os.path.dirname(__file__), "data", "cpu_trace.xplane.pb")


def _hand_made():
    ops = {0: [("fusion.1", 0, 10), ("all-reduce.2", 5, 20),
               ("fusion.3", 30, 40)],
           1: [("fusion.1", 0, 40), ("collective-permute-done.4", 35, 45)]}
    spans = [("bench.step", 0, 50), ("bench.data", 20, 30),
             ("bench.other", 100, 200)]
    return ops, spans


def test_busy_is_the_union_of_op_intervals():
    red = trace.reduce(*_hand_made(), "step")
    assert red["steps"] == 1
    assert red["window_s"] == pytest.approx(50e-9)
    # device 0: [0, 20] + [30, 40] = 30; device 1: [0, 45] = 45
    assert red["busy_s"] == pytest.approx((30 + 45) / 2 * 1e-9)


def test_collective_time_and_its_exposed_part():
    red = trace.reduce(*_hand_made(), "step")
    # device 0: all-reduce [5, 20], fusion covers [5, 10] -> exposed 10;
    # device 1: permute [35, 45], fusion covers [35, 40] -> exposed 5
    assert red["collective_s"] == pytest.approx((15 + 10) / 2 * 1e-9)
    assert red["collective_exposed_s"] == pytest.approx((10 + 5) / 2 * 1e-9)


def test_op_time_by_name_and_idle_gaps_by_host_span():
    red = trace.reduce(*_hand_made(), "step")
    assert red["opcode_s"]["fusion"] == pytest.approx((20 + 40) / 2 * 1e-9)
    assert red["op_s"]["fusion.1 fusion"] == pytest.approx((10 + 40) / 2 * 1e-9)
    assert trace.op_seconds(red, "all-reduce") == pytest.approx(7.5e-9)
    assert trace.op_seconds(red, "no-such-op") is None
    gaps = dict(red["idle_gaps"])
    # device 0: [20, 30] under bench.data, [40, 50] under bench.step;
    # device 1: [45, 50] under bench.step
    assert gaps["data"] == pytest.approx(10 / 2 * 1e-9)
    assert gaps["step"] == pytest.approx(15 / 2 * 1e-9)


def test_no_steps_reads_nothing():
    ops, spans = _hand_made()
    assert trace.reduce(ops, spans, "decode") == {"steps": 0}


TPU_KERNEL = ('fn.9 = (f32[48,48,1,128]{3,2,1,0:T(1,128)S(1)}) custom-call('
              'bf16[48,48,1,128]{3,2,1,0:T(2,128)(2,1)S(1)} %pad.4), '
              'custom_call_target="tpu_custom_call", operand_layout_'
              'constraints={bf16[48,48,1,128]{3,2,1,0}}')


def test_names_and_opcodes():
    assert trace.base_name("fusion.12") == "fusion"
    assert trace.base_name("all-gather-start.3.1") == "all-gather-start"
    assert trace.is_collective("collective-permute-done.2")
    assert trace.is_collective("reduce-scatter.7")
    assert not trace.is_collective("fusion.3")
    # a TPU trace names each op by its HLO text
    assert trace.instruction(TPU_KERNEL) == "fn.9"
    assert trace.opcode(TPU_KERNEL) == "custom-call:tpu_custom_call"
    ar = "all-reduce.3 = f32[] all-reduce(f32[] %x), replica_groups={}"
    assert trace.opcode(ar) == "all-reduce" and trace.is_collective(ar)
    rs = ("reshape.80 = bf16[3360,327680]{1,0:T(8,128)(2,1)} "
          "reshape(bf16[8601600,128]{1,0:T(8,128)(2,1)} %fusion.63)")
    assert trace.opcode(rs) == "reshape" and not trace.is_collective(rs)


def test_kernel_time_by_custom_call_target():
    ops = {0: [(TPU_KERNEL, 0, 30), ("fusion.2 = f32[8]{0} fusion(f32[8]{0} "
                                     "%a), kind=kLoop", 30, 40)]}
    red = trace.reduce(ops, [("bench.decode", 0, 40)], "decode")
    assert trace.op_seconds(red, "tpu_custom_call",
                            "opcode_s") == pytest.approx(30e-9)


def test_recorded_cpu_trace():
    ops, spans = trace.extract(RECORDED)
    red = trace.reduce(ops, spans, "step")
    assert red["steps"] == 3
    lo = min(s for n, s, _ in spans if n == "bench.step")
    hi = max(e for n, _, e in spans if n == "bench.step")
    assert red["window_s"] == pytest.approx((hi - lo) * 1e-9)
    (evs,) = ops.values()
    want = sum(e - s for s, e in trace.union(
        [(max(s, lo), min(e, hi)) for _, s, e in evs if e > lo and s < hi]))
    assert red["busy_s"] == pytest.approx(want * 1e-9)
    assert 0 < red["busy_s"] < red["window_s"]
    assert 0 < red["collective_exposed_s"] <= red["collective_s"]
    assert red["opcode_s"]["dot_general"] > 0
    # the 2 ms host sleep inside each bench.data span is the longest gap
    assert red["idle_gaps"][0][0] == "data"
    assert red["idle_gaps"][0][1] >= 3 * 2e-3

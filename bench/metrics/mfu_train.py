"""Whole training step's model FLOP/s over the chips' bf16 peak (%):
FLOPs the algorithm needs per step (6 * matmul params * tokens plus causal
attention, no recomputation) times the traced steps, over the traced
window times chips times peak."""


def read(r):
    if not r["peaks"]:
        return None
    t = r["trace"]
    if not t.get("steps") or not t.get("window_s"):
        return None
    done = r["inputs"]["step_flops"] * t["steps"]
    return 100.0 * done / (t["window_s"] * r["chips"] * r["peaks"]["bf16_flops"])

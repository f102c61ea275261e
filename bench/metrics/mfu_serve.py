"""Whole decode step's share of the chip's roofline (%): for each traced
step the larger of the FLOPs it needs over the bf16 peak and the bytes it
needs (the weights once, at their stored precision, and the live K/V at
the key/value heads and the sequences' real lengths) over the HBM
bandwidth, summed, over the traced window."""


def read(r):
    if not r["peaks"]:
        return None
    t, need = r["trace"], r["inputs"]
    if not t.get("steps") or not t.get("window_s") or not need.get("need"):
        return None
    pk = r["peaks"]
    bound = sum(max(n["flops"] / pk["bf16_flops"],
                    n["bytes"] / pk["hbm_bytes_per_s"]) for n in need["need"])
    return 100.0 * bound / t["window_s"]

"""The flash-decode kernel's share of its roofline (%): for each traced
step the larger of the attention's needed FLOPs over the bf16 peak and its
needed bytes (live K/V at the key/value heads and real lengths, plus q
and the output) over the HBM bandwidth, summed, over the kernel's device
time.  The TPU trace gives a Pallas kernel no name of its own (its op is
``custom-call`` to ``tpu_custom_call``); the decode step holds one kernel,
this one, so the kernel is found by that target."""

KERNEL = "tpu_custom_call"


def read(r):
    if not r["peaks"]:
        return None
    from bench.trace import op_seconds

    t, need = r["trace"], r["inputs"]
    secs = op_seconds(t, KERNEL, "opcode_s")
    if not secs or not need.get("need"):
        return None
    pk = r["peaks"]
    bound = sum(max(n["attn_flops"] / pk["bf16_flops"],
                    n["attn_bytes"] / pk["hbm_bytes_per_s"])
                for n in need["need"])
    return 100.0 * bound / secs

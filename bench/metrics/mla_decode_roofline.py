"""The latent-attention kernel's share of its roofline (%): for each traced
step the larger of the attention's needed FLOPs over the bf16 peak and its
needed bytes (the live latent rows at the sequences' real lengths, plus
each head's query and output) over the HBM bandwidth, summed, over the
kernel's device time.  The kernel is found by its own name: on a TPU its
instruction is the ``mla_decode`` custom call.  In interpret mode (the
benchmark's CPU tests) it runs as the ``while`` loops of the step's
``mla_decode`` scope, and those are read instead."""

KERNEL = "mla_decode"


def kernel_seconds(r):
    from bench.trace import base_name

    t = r["trace"]
    ops = t.get("op_s", {})
    named = [v for k, v in ops.items()
             if base_name(k.split(" ", 1)[0]) == KERNEL]
    if named:
        return sum(named)
    scopes = r["inputs"].get("scopes", {})
    loops = [v for k, v in ops.items()
             if base_name(k.split(" ", 1)[0]) == "while"
             and scopes.get(k.split(" ", 1)[0]) == KERNEL]
    return sum(loops) if loops else None


def read(r):
    if not r["peaks"]:
        return None
    secs, need = kernel_seconds(r), r["inputs"].get("need")
    if not secs or not need:
        return None
    pk = r["peaks"]
    bound = sum(max(n["attn_flops"] / pk["bf16_flops"],
                    n["attn_bytes"] / pk["hbm_bytes_per_s"]) for n in need)
    return 100.0 * bound / secs

"""Device time of collective operations (HLO all-reduce, all-gather,
reduce-scatter, collective-permute, all-to-all, send/recv) per training
step (ms), averaged over the chips."""


def read(r):
    from bench.metrics._shares import per_step_ms
    return per_step_ms(r, "collective_s")

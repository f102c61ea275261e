"""The part of the collective time per training step (ms) in which no
other operation ran on the same chip, averaged over the chips."""


def read(r):
    from bench.metrics._shares import per_step_ms
    return per_step_ms(r, "collective_exposed_s")

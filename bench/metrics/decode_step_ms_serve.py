"""Device busy time per decode step (ms): the union of the operation
intervals in the traced window over the number of decode steps in it."""


def read(r):
    from bench.metrics._shares import per_step_ms
    return per_step_ms(r, "busy_s")

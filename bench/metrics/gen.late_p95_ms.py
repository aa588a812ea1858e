"""How late the load generator submitted requests: p95 of submit time
minus due time over the window, host clock, ms."""


def read(run):
    return run.pctl("gen.late_s", 95)

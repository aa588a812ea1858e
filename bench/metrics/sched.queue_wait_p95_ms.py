"""p95 over the window's requests of admission minus due time, from
the engine's own request records on the wall clock, ms."""


def read(run):
    return run.pctl("sched.queue_wait_s", 95)

"""Host time inside each ``rk3_step`` call (enqueue, staging, dispatch),
mean over the window's steps; the dt read is outside the span."""


def read(run):
    return sum(run.host_s) * 1e3 / len(run.host_s)

"""Wall time of the window over the RK3 steps completed in it (host clock,
window closed by ``block_until_ready``)."""


def read(run):
    return run.window_s * 1e3 / run.steps

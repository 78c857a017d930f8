"""Delta of the runner's ``stats["kernel_launches"]`` over the window,
per step."""


def read(run):
    return run.launches / run.steps

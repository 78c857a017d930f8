"""Delta of the runner's ``stats["staging_s"]`` (the aggregation
executor's own clock around staging) over the window, per step; nothing
to read where no aggregation executor stages."""


def read(run):
    if run.staging_s is None:
        return None
    return run.staging_s * 1e3 / run.steps

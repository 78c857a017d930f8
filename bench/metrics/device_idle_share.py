"""1 minus the union of device-op intervals over the traced window."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace["idle_share"]

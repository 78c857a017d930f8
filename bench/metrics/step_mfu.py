"""The chip's least time for one step's hydro work (frozen counts over
published peaks, see ``roofline.py``) as a share of the measured step
time of the window."""


def read(run):
    return 100.0 * run.least_step_s() / (run.window_s / run.steps)

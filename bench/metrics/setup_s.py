"""Process start to the start of the window: imports, device start, state,
warm-up and compiles or compile-cache loads."""


def read(run):
    return run.setup_s

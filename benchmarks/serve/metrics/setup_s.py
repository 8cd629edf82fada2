"""Set-up: process start to the window's opening (weights, warm-up, ramp)."""


def read(run):
    return run.setup_s

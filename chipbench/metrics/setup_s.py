"""Seconds from the process start to the window: start-up, data, ground
truth, build and the warm-up that compiles or loads the served program."""


def read(run):
    return run.setup_s

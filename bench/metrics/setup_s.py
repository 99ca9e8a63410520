"""Seconds from process start to the window's opening: import and backend
start, inputs from the seed, compile or cache load, warm-up."""


def read(run):
    return run.setup_s

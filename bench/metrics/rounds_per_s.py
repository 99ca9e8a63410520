"""Integrated rounds completed over the whole window, each run's ledger
validated: all rounds / the window's span on the host clock."""


def read(run):
    return run.window["rounds"] / run.window["span_s"]

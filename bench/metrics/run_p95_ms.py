"""95th percentile of one engine call's wall, from the call to the
validated ledger, over every call in the window (host clock)."""
import numpy as np


def read(run):
    return float(np.percentile(run.window["walls_s"], 95)) * 1e3

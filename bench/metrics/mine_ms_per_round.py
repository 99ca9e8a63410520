"""Device milliseconds per round in the mine stage (the PoW race over the
client axis, the hash link): the union of the device intervals of the
ops under the ``mine`` stage scope within the traced window, per traced
round (``program_trace``)."""
import program_trace


def read(run):
    return program_trace.stage_ms_per_round(run, "mine")

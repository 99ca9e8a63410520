"""Device milliseconds per round in the communicate stage (header digest,
divergence, the topology mix): the union of the device intervals of the
ops under the ``communicate`` stage scope within the traced window, per
traced round (``program_trace``)."""
import program_trace


def read(run):
    return program_trace.stage_ms_per_round(run, "communicate")

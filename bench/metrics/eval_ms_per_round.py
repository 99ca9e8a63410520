"""Device milliseconds per round in the finalize stage (the global-loss
eval, the next carry): the union of the device intervals of the ops
under the ``finalize`` stage scope within the traced window, per traced
round (``program_trace``)."""
import program_trace


def read(run):
    return program_trace.stage_ms_per_round(run, "finalize")

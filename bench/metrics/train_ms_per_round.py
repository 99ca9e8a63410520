"""Device milliseconds per round in local training (the tau local GD steps
of every client): the union of the device intervals of the ops under the
``local_train`` stage scope within the traced window, per traced round
(``program_trace``)."""
import program_trace


def read(run):
    return program_trace.stage_ms_per_round(run, "local_train")

"""Device-idle milliseconds per round while the host is in a driver phase
or a fetch: the device's idle stretches within the traced window whose
innermost program span is ``blade.plan``, ``init``, ``dispatch``,
``history``, ``cohort`` or ``fetch``, per traced round
(``program_trace``)."""
import program_trace


def read(run):
    got = program_trace.read(run)
    if not got or not got["n_devices"]:
        return None
    return program_trace.per_round_ms(run, sum(
        got["idle"].get(name, 0.0)
        for name in program_trace.DRIVER + ("blade.fetch",)))

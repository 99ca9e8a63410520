"""Host milliseconds per round in the drivers' own phases: the self time
of the program's ``blade.plan``, ``init``, ``dispatch``, ``history`` and
``cohort`` spans within the traced window, per traced round
(``program_trace``). Read only where the trace has device planes: on a
host-only backend the dispatch runs the program itself, so the host's
phases are not apart from the device's work."""
import program_trace


def read(run):
    got = program_trace.read(run)
    if not got or not got["n_devices"]:
        return None
    return program_trace.per_round_ms(run, sum(
        got["spans"].get(name, {}).get("self_s", 0.0)
        for name in program_trace.DRIVER))

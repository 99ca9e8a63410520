"""Host milliseconds per round spent building the cohort's batch
(``CohortDataSource.cohort_batch``), from the benchmark's span around the
batch callable, in the traced calls."""


def read(run):
    if not run.rounds_traced or not run.spans.seconds("data"):
        return None
    return 1e3 * run.spans.seconds("data") / run.rounds_traced

"""Host milliseconds per round in the population store's gather and
scatter (``rounds.PopulationStore``), from the benchmark's spans, in the
traced calls. The scatter's span opens once the round's output is ready."""


def read(run):
    if not run.rounds_traced or not run.spans.seconds("store"):
        return None
    return 1e3 * run.spans.seconds("store") / run.rounds_traced

"""Host milliseconds per round in the ledger layer (``repro.core.chain``):
the benchmark's spans around the ledger replay or block appends and around
each call's ``validate_chain``, in the traced calls."""


def read(run):
    if not run.rounds_traced or not run.spans.seconds("ledger"):
        return None
    return 1e3 * run.spans.seconds("ledger") / run.rounds_traced

"""Share of the traced window in which a chip runs no operation, from the
device trace, averaged over the chips used (percent)."""


def read(run):
    if not run.trace or not run.trace.get("n_devices"):
        return None
    return 100.0 * run.trace["idle_share"]

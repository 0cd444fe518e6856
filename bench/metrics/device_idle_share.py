"""Device: the share of the traced slice in which no operation ran on
the chip, in percent."""
from bench import trace as trace_lib


def read(run):
    if run.trace is None:
        return None
    return 100.0 * trace_lib.idle_share(run.trace)

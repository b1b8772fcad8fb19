"""Share of the traced slice in which no program ran on the device, in %,
in a cell whose latency is set by prefill (the device is shared by both
stages)."""
from bench import trace as T


def read(run):
    if run.trace is None or run.trace_window is None:
        return None
    return 100.0 * T.idle_share(run.trace, run.trace_window)

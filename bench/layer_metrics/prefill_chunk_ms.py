"""Mean device milliseconds of one execution of the prefill program
(``jit__prefill_impl``) in the traced slice."""
from bench import trace as T


def read(run):
    if run.trace is None:
        return None
    d = T.program_durations(run.trace, "jit__prefill_impl", run.trace_window)
    return sum(d) / len(d) * 1e3 if d else None

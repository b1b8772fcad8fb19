"""Mean device milliseconds of one execution of the decode program
(``jit__decode_impl``) in the traced slice."""
from bench import trace as T


def read(run):
    if run.trace is None:
        return None
    d = T.program_durations(run.trace, "jit__decode_impl", run.trace_window)
    return sum(d) / len(d) * 1e3 if d else None

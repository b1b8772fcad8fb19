"""Mean number of active slots per decode call of the output stage in the
window (counted by the benchmark's wrapper on ``PagedRunner.decode``)."""


def read(run):
    t0, t1 = run.window
    calls = [len(lens) for s, _, lens in
             run.recorder.decodes.get(run.system.output, ()) if t0 <= s < t1]
    return sum(calls) / len(calls) if calls else None

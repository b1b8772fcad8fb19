"""Model FLOP utilization of the output stage's engine step, in %: the
model FLOPs of the tokens its steps processed in the window
(``bench/counts.py``: linear layers, LM head and attention over each
token's context) over the host wall time of those steps times the chip's
bf16 peak."""


def read(run):
    t0, t1 = run.window
    steps = [(a, b, f) for a, b, f in
             run.recorder.steps.get(run.system.output, ()) if t0 <= a < t1]
    wall = sum(b - a for a, b, _ in steps)
    flops = sum(f for _, _, f in steps)
    if not flops or wall <= 0:
        return None
    return 100.0 * flops / (wall * run.peaks["bf16_flops"])

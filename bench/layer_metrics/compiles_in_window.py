"""Backend compiles (persistent-cache loads included) JAX reported while
the window was open; anything above 0 means warm-up missed a shape."""


def read(run):
    return float(run.clock.between(*run.window)[0])

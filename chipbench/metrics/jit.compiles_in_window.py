"""Backend compilations JAX reported inside the window (a program compiled
there is set-up work the window should not hold)."""


def read(run):
    return float(run.compiles.count_between(*run.window))

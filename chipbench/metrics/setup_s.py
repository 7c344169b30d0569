"""Set-up: from process start to the window's start, compilation, weight
making, warm-up and the steps a training cell checks included."""


def read(run):
    return run.window[0] - run.t_process

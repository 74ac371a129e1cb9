"""Process start to the window's start: imports, weights, engine, warm-up
and every compile."""


def read(run):
    return run.setup_s

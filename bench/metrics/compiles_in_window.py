"""Backend compiles and persistent-cache loads that JAX reported inside
the window (the counting of chip_smoke.py's CompileMeter)."""


def read(run):
    return float(run.counters.compiles)

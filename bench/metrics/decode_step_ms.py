"""Device time per batched decode program (a decode program launched
inside ``bench.decode``)."""
from bench.metrics import device_seconds


def read(run):
    if run.trace is None:
        return None
    secs, n = device_seconds(run, "decode", "bench.decode")
    return 1e3 * secs / n if n else None

"""Device time of decode programs launched inside ``bench.admit`` (the
prefix path's token-at-a-time suffix), over device busy time."""
from bench import tracing
from bench.metrics import device_seconds


def read(run):
    if run.trace is None:
        return None
    busy = tracing.busy_s(run.trace)
    if busy <= 0:
        return None
    secs, _ = device_seconds(run, "decode", "bench.admit")
    return 100.0 * secs / busy

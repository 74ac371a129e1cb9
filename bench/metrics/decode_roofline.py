"""Least time for the bytes a batched decode step needs (bench/flops.py:
weights a token of the batch reaches, output head, KV of the active slots'
real lengths) at the chip's HBM bandwidth, over the measured device time
per batched decode program.  Memory bound: a decode step's operations per
byte are far below the chip's ridge point."""
from bench.metrics import device_seconds


def read(run):
    c = run.counters
    if run.trace is None or not c.decode_steps:
        return None
    secs, n = device_seconds(run, "decode", "bench.decode")
    if not n:
        return None
    least = c.decode_bytes / c.decode_steps / run.peak["hbm_bytes_per_s"]
    return 100.0 * least / (secs / n)

"""FLOPs a full prefill needs (bench/flops.py, from the prompt lengths)
over the device time of the prefill programs and the chip's bf16 peak."""
from bench.metrics import device_seconds


def read(run):
    c = run.counters
    if run.trace is None or not c.full_prefills:
        return None
    secs, n = device_seconds(run, "prefill", "bench.admit")
    if not n:
        return None
    per_call = c.prefill_flops / c.full_prefills
    return 100.0 * per_call / (secs / n) / run.peak["bf16_flops"]

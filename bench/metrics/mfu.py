"""FLOPs of every token computed in the window (prompt tokens not served
from the cache, suffix tokens, output tokens; bench/flops.py) over the
window's seconds and the chip's bf16 peak: the whole step's share of the
peak."""


def read(run):
    if run.counters.flops <= 0:
        return None
    return 100.0 * run.counters.flops / run.seconds / run.peak["bf16_flops"]

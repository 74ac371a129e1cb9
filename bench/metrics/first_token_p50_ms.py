"""Median time from a call's submission to its first token, over the calls
whose first token came inside the window (in a traced run, the part of the
window that the trace holds)."""
import statistics


def read(run):
    lo, hi = run.window
    waits = [c.times[0] - c.submitted for c in run.calls
             if c.times and lo <= c.times[0] <= hi]
    return 1e3 * statistics.median(waits) if waits else None

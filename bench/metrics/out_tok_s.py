"""Output tokens emitted inside the window, finished or not, per second."""


def read(run):
    lo, hi = run.window
    n = sum(lo <= t <= hi for c in run.calls for t in c.times)
    return n / run.seconds

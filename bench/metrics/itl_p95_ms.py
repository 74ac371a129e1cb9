"""95th percentile of the gaps between consecutive output tokens of one
call, over every gap whose two tokens came inside the window."""
import numpy as np


def read(run):
    lo, hi = run.window
    gaps = [b - a for c in run.calls for a, b in zip(c.times, c.times[1:])
            if lo <= a and b <= hi]
    return 1e3 * float(np.percentile(gaps, 95)) if gaps else None

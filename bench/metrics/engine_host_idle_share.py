"""Share of the traced window in which the first device runs no program
while the host is inside the engine (some ``engine.*`` span) and not
waiting for a result (outside every ``engine.sync`` span): the device
idle that the engine's own host work causes.  None where the trace holds
no ``engine.*`` span."""
from bench import tracing

PREFIX = "engine."
SYNC = "engine.sync"


def overlap(a, b):
    """Where two sorted lists of disjoint intervals both hold."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def seconds(intervals):
    return sum(e - s for s, e in intervals)


def read(run):
    trace = run.trace
    if trace is None or not trace.programs or trace.window_s <= 0:
        return None
    engine = [e for e in trace.host if e.name.startswith(PREFIX)]
    if not engine:
        return None
    lo, hi = trace.window
    busy = tracing.device_intervals(trace, sorted(trace.programs)[0])
    edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
    idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    in_engine = overlap(idle, tracing.union(tracing.clip(engine, lo, hi)))
    syncs = tracing.union(tracing.clip(
        [e for e in engine if e.name == SYNC], lo, hi))
    host = seconds(in_engine) - seconds(overlap(in_engine, syncs))
    return 100.0 * host / trace.window_s

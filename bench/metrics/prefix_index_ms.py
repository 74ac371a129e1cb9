"""Host time in the engine's prefix index per admission: the seconds of
the ``engine.prefix_index`` spans (lookup and slot invalidation at
admission, and every insert, the completion inserts among them) that
began inside the traced window, over the ``engine.admit`` spans that
began in it.  None where the trace holds no ``engine.admit`` span."""

INDEX = "engine.prefix_index"
ADMIT = "engine.admit"


def read(run):
    trace = run.trace
    if trace is None:
        return None
    lo, hi = trace.window
    began = [e for e in trace.host if lo <= e.start < hi]
    admits = sum(e.name == ADMIT for e in began)
    if not admits:
        return None
    secs = sum(e.end - e.start for e in began if e.name == INDEX)
    return 1e3 * secs / admits

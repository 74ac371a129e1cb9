"""Median time a call waited in the engine's queue: the engine's own
stamps, ``ServeRequest.admitted_at - submitted_at``, never compared with
the driver's clock.  The calls are those whose first token came inside
the window, as ``first_token_p50_ms`` chooses them.  None where the
engine stamps no request."""
import statistics


def read(run):
    lo, hi = run.window
    waits = []
    for c in run.calls:
        if c.times and lo <= c.times[0] <= hi:
            submitted = getattr(c.req, "submitted_at", None)
            admitted = getattr(c.req, "admitted_at", None)
            if submitted is not None and admitted is not None:
                waits.append(admitted - submitted)
    return 1e3 * statistics.median(waits) if waits else None

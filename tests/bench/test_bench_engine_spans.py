"""The readers of the engine's own spans and stamps
(``bench/metrics/engine_host_idle_share.py``, ``prefix_index_ms.py``,
``queue_wait_p50_ms.py``), on a hand-made trace and run whose answers are
worked out by hand, and once more on a 0.1 ms grid."""
from types import SimpleNamespace

import numpy as np
import pytest

from bench import tracing
from bench.metrics import (engine_host_idle_share, prefix_index_ms,
                           queue_wait_p50_ms)
from bench.tracing import Event, Program, Trace

DEV = "/device:TPU:0"


def engine_trace():
    """Busy [0,1] [2,3] [5,6] [8,9]; the profiler's buffer cut the window
    at 9.5, so it is idle over [1,2] [3,5] [6,8] [9,9.5]."""
    t = Trace()
    t.programs[DEV] = [Program("jit_batched_decode(1)", 0.0, 1.0),
                       Program("jit_batched_decode(1)", 2.0, 3.0),
                       Program("jit_prefill(2)", 5.0, 6.0),
                       Program("jit_batched_decode(1)", 8.0, 9.0)]
    t.spans = [Event("bench.window", 0.0, 10.0),
               Event("bench.decode", 0.5, 2.0),
               Event("bench.driver", 3.0, 4.0),
               Event("bench.admit", 4.0, 5.5),
               Event("bench.decode", 7.5, 8.0),
               Event("bench.admit", 9.2, 9.9)]
    t.host = [
        # idle [1,2]: the sync waits out [1,1.5] (not counted), the
        # bookkeeping holds the device [1.5,2] (0.5 s)
        Event("engine.decode", 0.5, 2.0),
        Event("engine.dispatch", 0.5, 0.6),
        Event("engine.sync", 0.6, 1.5),
        Event("np.asarray(jax.Array)", 0.7, 1.4),
        Event("engine.bookkeep", 1.5, 2.0),
        Event("engine.prefix_index", 1.6, 1.8),
        # idle [3,4]: the driver's bookkeeping, outside the engine
        # idle [4,5]: admission, [4,4.6] on the host (0.6 s), then a sync
        Event("engine.admit", 4.0, 5.5),
        Event("engine.prefix_index", 4.0, 4.2),
        Event("engine.prefill", 4.2, 4.6),
        Event("engine.sync", 4.6, 5.5),
        # idle [6,8]: a decode that starts late, [7.5,8] (0.5 s)
        Event("engine.decode", 7.5, 8.0),
        # idle [9,9.5]: an admission that straddles the cut, [9.2,9.5]
        # (0.3 s); its index span straddles it too and counts whole
        Event("engine.admit", 9.2, 9.6),
        Event("engine.prefix_index", 9.4, 9.55),
        # began after the cut: not counted
        Event("engine.admit", 9.7, 9.9),
        Event("engine.prefix_index", 9.7, 9.8)]
    t.window = (0.0, 9.5)
    return t


def run_of(trace):
    return SimpleNamespace(trace=trace)


def test_host_idle_counts_engine_host_work_only():
    assert engine_host_idle_share.read(run_of(engine_trace())) == \
        pytest.approx(100.0 * (0.5 + 0.6 + 0.5 + 0.3) / 9.5)


STEP = 1e-4


def mask(events, lo, hi):
    grid = np.zeros(int(round((hi - lo) / STEP)), bool)
    for e in events:
        a, b = max(e.start, lo), min(e.end, hi)
        if b > a:
            grid[int(round((a - lo) / STEP)):int(round((b - lo) / STEP))] = 1
    return grid


def test_host_idle_agrees_with_a_grid():
    t = engine_trace()
    lo, hi = t.window
    idle = ~mask(t.programs[DEV], lo, hi)
    engine = mask([e for e in t.host if e.name.startswith("engine.")],
                  lo, hi)
    sync = mask([e for e in t.host if e.name == "engine.sync"], lo, hi)
    share = 100.0 * (idle & engine & ~sync).sum() * STEP / t.window_s
    assert engine_host_idle_share.read(run_of(t)) == \
        pytest.approx(share, abs=1e-3)


def test_idle_gaps_are_named_by_the_engine_span_they_fall_in():
    gaps = tracing.idle_gaps(engine_trace())
    assert [n for n, _ in gaps] == ["bench.admit / engine.prefix_index",
                                    "outside bench spans",
                                    "bench.decode / engine.bookkeep",
                                    "bench.admit / engine.admit"]
    assert [s for _, s in gaps] == pytest.approx([2.0, 2.0, 1.0, 0.5])


def test_prefix_index_per_admission():
    # index spans that began in [0, 9.5): 0.2 + 0.2 + 0.15 s, over the two
    # admissions that began there
    assert prefix_index_ms.read(run_of(engine_trace())) == \
        pytest.approx(1e3 * 0.55 / 2)


def test_readers_are_silent_without_engine_spans():
    t = engine_trace()
    t.host = [e for e in t.host if not e.name.startswith("engine.")]
    assert engine_host_idle_share.read(run_of(t)) is None
    assert prefix_index_ms.read(run_of(t)) is None
    assert engine_host_idle_share.read(run_of(None)) is None
    assert prefix_index_ms.read(run_of(None)) is None


def call(first_token, submitted_at=None, admitted_at=None):
    req = SimpleNamespace(submitted_at=submitted_at, admitted_at=admitted_at)
    return SimpleNamespace(times=[] if first_token is None
                           else [first_token, first_token + 0.02], req=req)


def test_queue_wait_is_the_median_of_the_engines_stamps():
    run = SimpleNamespace(window=(10.0, 20.0), calls=[
        call(11.0, 100.0, 100.4),  # the engine's clock, not the driver's
        call(12.0, 100.0, 101.0),
        call(19.5, 103.0, 103.1),
        call(9.0, 90.0, 99.0),  # first token before the window
        call(21.0, 104.0, 110.0),  # after it
        call(None, 105.0, None)])  # waiting still
    assert queue_wait_p50_ms.read(run) == pytest.approx(400.0)


def test_queue_wait_is_silent_where_the_engine_stamps_nothing():
    run = SimpleNamespace(window=(10.0, 20.0), calls=[
        SimpleNamespace(times=[11.0], req=SimpleNamespace())])
    assert queue_wait_p50_ms.read(run) is None

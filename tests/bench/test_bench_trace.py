"""The reduction from a profiler trace to busy time, idle gaps and
per-program device time (bench/tracing.py)."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import tracing
from bench.tracing import Event, Program, Trace

DEV = "/device:TPU:0"


def small_trace():
    t = Trace()
    progs = [Program("jit__prefill_fn(7)", 0.95, 1.0),
             Program("jit_decode_step(1)", 1.0, 1.5),
             Program("jit_decode_step(8)", 2.05, 2.35),
             # launched in the last decode span; the device's clock puts
             # it in the gap before, and its program's majority decides
             Program("jit_decode_step(8)", 9.6, 9.75),
             Program("jit_decode_step(8)", 9.9, 10.4)]
    t.programs[DEV] = progs
    t.spans = [Event("bench.window", 0.0, 10.5),
               Event("bench.admit", 0.9, 1.6),
               Event("bench.driver", 1.6, 2.0),
               Event("bench.decode", 2.0, 2.4),
               Event("bench.decode", 9.8, 10.5)]
    t.host = [Event("np.asarray(jax.Array)", 1.7, 1.9)]
    t.window = (0.0, 10.0)
    tracing.attribute(t)
    return t


def test_busy_idle_and_programs_of_a_known_trace():
    t = small_trace()
    assert tracing.busy_s(t) == pytest.approx(0.05 + 0.5 + 0.3 + 0.15 + 0.1)
    # holes in [0, 10]: before the prefill, between the two phases, after
    # the second decode, and between the last two programs
    gaps = tracing.idle_gaps(t)
    assert [g[1] for g in gaps] == pytest.approx([7.25, 0.95, 0.55, 0.15])
    assert [g[0] for g in gaps][:3] == [
        "outside bench spans", "outside bench spans",
        "bench.driver / np.asarray(jax.Array)"]
    per = tracing.program_seconds(t)
    assert per[("jit_decode_step(1)", "bench.admit")] == pytest.approx(
        (0.5, 1))
    # a program counts whole when it began inside the window
    assert per[("jit_decode_step(8)", "bench.decode")] == pytest.approx(
        (0.95, 3))
    assert per[("jit__prefill_fn(7)", "bench.admit")] == pytest.approx(
        (0.05, 1))
    top = tracing.top_programs(t)
    assert top[0][0] == "jit_decode_step(8) @ bench.decode"


# read off this trace once, by bench/tracing.py, and checked by hand against
# the 0.1 us grid below: 4.20 ms of programs in the 0.3 s window, 2.67 ms of
# them the 89 batch-1 suffix decodes launched in bench.admit (a prefix-path
# run); the 18 batched decodes all go to bench.decode
KNOWN_BUSY_S = 0.004201836999999773
KNOWN_SUFFIX_S = 0.0026709589999999762


def recorded():
    doc = json.loads((Path(__file__).parent / "data" /
                      "tiny_trace.json").read_text())
    trace = tracing.from_events({doc["device"]: doc["programs"]},
                                doc["host"], doc["seconds"])
    return doc, trace


STEP = 1e-7


def brute_busy(rows, lo, hi):
    """Busy mask of the window on a 0.1 us grid: an independent union."""
    grid = np.zeros(int(round((hi - lo) / STEP)), bool)
    for _, s, e in rows:
        a, b = max(s, lo), min(e, hi)
        if b > a:
            grid[int(round((a - lo) / STEP)):int(round((b - lo) / STEP))] = 1
    return grid


def test_reduction_of_a_trace_recorded_on_the_chip():
    doc, t = recorded()
    lo, hi = t.window
    assert (lo, hi) == pytest.approx((0.0, 0.3))
    grid = brute_busy(doc["programs"], lo, hi)
    assert tracing.busy_s(t) == pytest.approx(grid.sum() * STEP, abs=1e-5)
    assert tracing.busy_s(t) == pytest.approx(KNOWN_BUSY_S, rel=1e-6)
    # the longest idle run of the grid is the longest gap
    edges = np.flatnonzero(np.diff(np.r_[1, grid.astype(int), 1]))
    runs = (edges[1::2] - edges[::2]) * STEP
    gaps = tracing.idle_gaps(t)
    assert gaps[0][1] == pytest.approx(runs.max(), abs=1e-6)
    assert all(name.startswith(("bench.", "outside")) for name, _ in gaps)
    # per program: every program that began in the window, counted whole
    per = tracing.program_seconds(t)
    began = [(n, e - s) for n, s, e in doc["programs"] if lo <= s < hi]
    assert sum(k for _, k in per.values()) == len(began)
    assert sum(v for v, _ in per.values()) == pytest.approx(
        sum(d for _, d in began))
    in_admit = sum(v for (name, span), (v, _) in per.items()
                   if "decode_step" in name and span == "bench.admit")
    assert in_admit == pytest.approx(KNOWN_SUFFIX_S, rel=1e-6)
    assert sum(n for (name, span), (_, n) in per.items()
               if "decode_step" in name and span == "bench.decode") == 18

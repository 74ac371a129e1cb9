"""Run one cell of BENCHMARK.json once, on the chip.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the program's model at the configuration's published sizes
with weights drawn from ``--seed`` (``bench/weights.py``), starts one
``ServingEngine``, and runs every shape the cell's traffic will use once:
each full-prefill length of its mix and the batched decode.  Then the
closed-loop driver (``bench/driver.py``) serves the mix for ``--seconds``.
With ``--trace 1`` the window is recorded by the profiler and the per-layer
metrics are read from it, over the part of the window the trace holds;
with ``--trace 0`` the end-to-end metrics are printed.

Once the window has closed, the engine is freed and a sample of the calls
it finished is compared with the plain reference (``bench/check.py``) under
the cell's limit (``bench/limits/<cell>.json``).  The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` also ``breakdown``, and last
``compared``: each number compared beside its limit (also the last lines of
standard error).

Exits non-zero without a result when JAX finds no TPU or fewer chips than
the cell asks for.  The persistent compilation cache is kept at
``<checkout>/.jax_cache``; a traced run's profile is written under
``<checkout>/.bench_out`` and deleted once read.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import ROOT, check, tracing  # noqa: E402
from bench.cells import Cell, reader, resolve  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
OUT_DIR = ROOT / ".bench_out"
PEAKS = Path(__file__).resolve().parent / "peaks.json"
LIMITS = Path(__file__).resolve().parent / "limits"
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_hits")


def use_compile_cache() -> None:
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # the directory holds this checkout's programs only: a size limit set
    # for a shared cache would evict them in turn, and every run would
    # compile again
    jax.config.update("jax_compilation_cache_max_size", -1)
    # keep small programs too: merging a prefill into its slot compiles one
    # small program per prompt length, each would otherwise compile again
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


class CompileLog:
    """Host-clock times of JAX's compile events (chip_smoke.py's
    CompileMeter, kept as times so a window can count its own)."""

    def __init__(self):
        self.times = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == COMPILE_EVENTS[0]:
            self.times.append(time.perf_counter())

    def _event(self, event, **_):
        if event == COMPILE_EVENTS[1]:
            self.times.append(time.perf_counter())

    def between(self, lo: float, hi: float) -> int:
        return sum(lo <= t <= hi for t in self.times)


def peaks(kind: str) -> dict:
    table = json.loads(PEAKS.read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {PEAKS}")
    return table[kind]


def limits(cell: str) -> dict:
    """The cell's limits: number compared -> limit (the file's other keys
    record the readings they were set from)."""
    table = json.loads((LIMITS / f"{cell}.json").read_text())
    return {k: v for k, v in table.items() if k in check.NUMBERS}


def program_model(cfg: dict):
    """The program's model at the configuration file's sizes."""
    from repro.configs.registry import get_config
    from repro.models import build_model

    sizes = dict(num_layers=cfg["num_hidden_layers"],
                 d_model=cfg["hidden_size"],
                 num_heads=cfg["num_attention_heads"],
                 num_kv_heads=cfg["num_key_value_heads"],
                 head_dim=cfg["head_dim"], d_ff=cfg["intermediate_size"],
                 vocab_size=cfg["vocab_size"], rope_theta=cfg["rope_theta"],
                 qkv_bias=bool(cfg.get("attention_bias")),
                 tie_embeddings=cfg["tie_word_embeddings"])
    return build_model(dataclasses.replace(get_config(cfg["registry"]),
                                           **sizes))


def warm_up(engine, traffic) -> None:
    """Run every shape the window will use once, and no other: each
    full-prefill length of the mix, and the batched decode."""
    from repro.serving.engine import ServeRequest

    from bench.traffic import full_prefill_lengths, host_rng

    rng = host_rng(traffic.seed, 3)
    for i, n in enumerate(full_prefill_lengths(traffic.mix)):
        prompt = rng.integers(0, traffic.vocab, n, dtype=np.int32)
        engine.submit(ServeRequest(-1 - i, prompt, max_new_tokens=2))
    engine.run_to_completion()


def serve(cell: Cell, seed: int, seconds: float, trace: bool, *,
          t_start: float = T_START, device_peaks: dict | None = None,
          breaker=None):
    """Set up, serve the window, read the cell's metrics, free the engine.
    Returns the result line without its verdict, the reference over the
    same weights, and the sample of finished calls it is to compare.
    Tests give ``device_peaks`` where a device has no entry, and
    ``breaker(engine)`` to break the engine after set-up."""
    from repro.serving.engine import ServingEngine

    from bench.driver import Driver
    from bench.traffic import Traffic
    from bench.weights import check_layout, make_weights

    cfg, dev = cell.config, jax.devices()[0]
    device_peaks = device_peaks or peaks(dev.device_kind)
    log = CompileLog()
    bundle = program_model(cfg)
    check_layout(bundle.shapes(), cfg)
    params = make_weights(bundle.shapes(), seed)
    engine = ServingEngine(bundle, params, slots=cfg["slots"],
                           max_len=cfg["max_len"])
    traffic = Traffic(cell.mix, cfg["vocab_size"], seed)
    warm_up(engine, traffic)
    if breaker is not None:
        breaker(engine)
    driver = Driver(engine, traffic, cfg)
    trace_dir = OUT_DIR / f"trace-{cell.name}"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        # host spans (TraceMe) only: Python's own function tracing would add
        # a million host events a window and slow the driver it measures
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    setup_s = time.perf_counter() - t_start
    driver.run(seconds)
    if trace:
        jax.profiler.stop_trace()
    mem = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": mem.get("peak_bytes_in_use")}

    lo, hi = driver.window
    calls = driver.calls
    finished = [c for c in calls if c.done_at is not None and c.done_at <= hi]
    driver.engine = None
    engine.cache = None
    del engine
    gc.collect()  # the engine's jitted methods hold it in a cycle

    breakdown, reduced, upto = {}, None, hi
    if trace:
        reduced = tracing.load(tracing.find_xplane(str(trace_dir)), seconds)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device.update(busy_s=tracing.busy_s(reduced),
                      window_s=reduced.window_s)
        breakdown["breakdown"] = {
            "device_ops": [list(r) for r in tracing.top_programs(reduced)],
            "idle_gaps": [list(r) for r in tracing.idle_gaps(reduced)]}
        # the profiler's buffer may end the trace before the window: the
        # per-layer metrics count only what the trace holds
        upto = lo + reduced.window_s
    counters = driver.tally.over(lo, upto)
    counters.compiles = log.between(lo, hi)
    run = SimpleNamespace(cfg=cfg, window=(lo, upto), seconds=upto - lo,
                          calls=calls, counters=counters, setup_s=setup_s,
                          peak=device_peaks, trace=reduced)
    metrics = {}
    for spec in (cell.per_layer if trace else cell.end_to_end):
        value = reader(spec["name"]).read(run)
        if value is not None:
            metrics[spec["name"]] = {"value": float(value),
                                     "unit": spec["unit"]}
    in_window = [c for c in calls if lo <= c.submitted <= hi]
    result = {"attempted": len(in_window),
              "failed": sum(c.done_at is not None and len(c.tokens) < c.max_new
                            for c in in_window),
              "metrics": metrics, "device": device, **breakdown}
    return (result, check.Reference(cfg, params),
            check.sample_calls(finished, seed))


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             cell_limits: dict | None = None, **kw) -> dict:
    """One run of the cell: ``serve``, then the served tokens of the sample
    against the reference under the cell's limits (``cell_limits`` where a
    test's cell has no file)."""
    result, reference, sample = serve(cell, seed, seconds, trace, **kw)
    gaps = reference.served_gaps(sample)
    verdict = check.compare(gaps, limits(cell.name) if cell_limits is None
                            else cell_limits)
    return {"correct": verdict.pop("ok"), **result,
            "readings": check.readings(gaps), "compared": verdict}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = resolve(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 3
    use_compile_cache()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    for name, entry in result["compared"].items():
        print(f"compared {name}: {entry['value']} (limit {entry['limit']})",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())

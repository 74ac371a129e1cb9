import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e).

For every (architecture × input shape × mesh) cell, lower + compile the
real step function — train_step for train shapes, prefill/serve_step for
inference shapes — against ShapeDtypeStruct inputs on the production
meshes, and record:

  * ``compiled.memory_analysis()``  (bytes per device — proves it fits),
  * ``compiled.cost_analysis()``    (FLOPs / bytes for §Roofline),
  * per-collective bytes parsed from the optimized HLO.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod-only]
Results land in experiments/dryrun/<arch>__<shape>__<mesh>.json.
"""
import argparse
import contextlib
import json
import re
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, InputShape, SHAPES_BY_NAME, shapes_for
from repro.distributed.sharding import fit_spec, tree_shardings
from repro.configs.registry import ASSIGNED, get_config
from repro.launch.mesh import make_production_mesh
from repro.models import build_model
from repro.training.optimizer import init_state, state_specs
from repro.training.train_loop import make_train_step

RESULTS_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")

DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
               "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8, "s16": 2,
               "u16": 2, "f8e4m3fn": 1, "f8e5m2": 1}


# ---------------------------------------------------------------------------
# input_specs: ShapeDtypeStruct stand-ins for every model input
# ---------------------------------------------------------------------------


def input_specs(cfg: ArchConfig, shape: InputShape, bundle) -> dict:
    B = shape.global_batch
    out = {}
    if shape.kind == "train":
        S = shape.seq_len
        out["tokens"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
        out["labels"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
    elif shape.kind == "prefill":
        out["tokens"] = jax.ShapeDtypeStruct((B, shape.seq_len), jnp.int32)
    else:  # decode: one new token against a seq_len cache
        out["tokens"] = jax.ShapeDtypeStruct((B,), jnp.int32)
    for name, fn in (bundle.extra_inputs or {}).items():
        out[name] = fn(B)
    return out


def pick_microbatches(cfg: ArchConfig, shape: InputShape, mesh) -> int:
    """Split the global batch so per-chip live activations stay bounded."""
    data_ways = 1
    for ax in ("pod", "data"):
        if ax in mesh.shape:
            data_ways *= mesh.shape[ax]
    per_shard = max(shape.global_batch // data_ways, 1)
    # target <= 2 sequences per data shard per microbatch; hybrid/SSM
    # archs carry extra f32 scan state (mamba/WKV chunk buffers), so give
    # them 1 sequence per shard per microbatch
    per_mb = 1 if cfg.ssm_state else 2
    mb = max(per_shard // per_mb, 1)
    while shape.global_batch % mb:
        mb -= 1
    return mb


# ---------------------------------------------------------------------------
# lowering per shape kind
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def _mesh_context(mesh):
    """Enter the mesh and its abstract mesh, which ``shard_hint`` reads."""
    with mesh, jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        yield


def lower_cell(cfg: ArchConfig, shape: InputShape, mesh, mesh_name: str):
    t0 = time.time()
    with _mesh_context(mesh):
        if shape.kind == "train":
            nmb = pick_microbatches(cfg, shape, mesh)
            bundle = build_model(cfg, num_microbatches=nmb)
            params = bundle.shapes()
            params_sh = tree_shardings(bundle.specs(), mesh, params)
            opt_state = jax.eval_shape(init_state, params)
            opt_sh = tree_shardings(state_specs(bundle.specs()), mesh,
                                    opt_state)
            batch = input_specs(cfg, shape, bundle)
            batch_sh = {k: NamedSharding(mesh, fit_spec(
                P(("pod", "data")), v.shape, mesh))
                for k, v in batch.items()}
            step = make_train_step(bundle)
            jitted = jax.jit(step,
                             in_shardings=(params_sh, opt_sh, batch_sh),
                             out_shardings=(params_sh, opt_sh, None),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(params, opt_state, batch)
        elif shape.kind == "prefill":
            bundle = build_model(cfg)
            params = bundle.shapes()
            params_sh = tree_shardings(bundle.specs(), mesh, params)
            batch = input_specs(cfg, shape, bundle)
            batch_sh = {k: NamedSharding(mesh, fit_spec(
                P(("pod", "data")), v.shape, mesh))
                for k, v in batch.items()}
            cache_sh = None
            if bundle.cache_spec_fn and bundle.cache_shape_fn:
                cache_shapes = bundle.cache_shape_fn(shape.global_batch,
                                                     shape.seq_len)
                cache_sh = tree_shardings(bundle.cache_spec_fn(), mesh,
                                          cache_shapes)
            jitted = jax.jit(bundle.prefill,
                             in_shardings=(params_sh, batch_sh),
                             out_shardings=(None, cache_sh))
            lowered = jitted.lower(params, batch)
        else:  # decode
            bundle = build_model(cfg)
            params = bundle.shapes()
            params_sh = tree_shardings(bundle.specs(), mesh, params)
            cache = bundle.cache_shape_fn(shape.global_batch, shape.seq_len)
            # §Perf: head-sharded decode cache when kv heads fill the
            # model axis (local attention, no seq-dim DUS resharding)
            model_ways = mesh.shape.get("model", 1)
            kv_layout = ("heads" if cfg.num_kv_heads % model_ways == 0
                         and not cfg.attn_free and not cfg.sliding_window
                         and os.environ.get("REPRO_KV_LAYOUT", "auto") != "seq"
                         else "seq")
            try:
                cache_specs = bundle.cache_spec_fn(kv_layout)
            except TypeError:
                cache_specs = bundle.cache_spec_fn()
            cache_sh = tree_shardings(cache_specs, mesh, cache)
            tokens = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)
            tok_sh = NamedSharding(mesh, fit_spec(P(("pod", "data")),
                                                  tokens.shape, mesh))
            pos = jax.ShapeDtypeStruct((), jnp.int32)
            jitted = jax.jit(bundle.decode_step,
                             in_shardings=(params_sh, cache_sh, tok_sh, None),
                             out_shardings=(None, cache_sh),
                             donate_argnums=(1,))
            lowered = jitted.lower(params, cache, tokens, pos)

        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled)
    from repro.analysis.hlo_stats import analyze_compiled
    hlo = analyze_compiled(compiled)
    # persist the optimized HLO so analyzer improvements can re-derive
    # stats without recompiling (repro.launch.reanalyze)
    import gzip
    hlo_dir = RESULTS_DIR / "hlo"
    hlo_dir.mkdir(parents=True, exist_ok=True)
    (hlo_dir / f"{cfg.name}__{shape.name}__{mesh_name}.hlo.gz").write_bytes(
        gzip.compress(compiled.as_text().encode()))
    return {
        "hlo_stats": {
            "flops": hlo.flops,
            "hbm_bytes": hlo.hbm_bytes,
            "collective_bytes": hlo.collective_bytes,
            "collective_counts": hlo.collective_counts,
            "total_collective_bytes": hlo.total_collective_bytes,
            "while_trip_counts": hlo.while_trip_counts,
        },
        "arch": cfg.name,
        "shape": shape.name,
        "kind": shape.kind,
        "mesh": mesh_name,
        "num_devices": mesh.devices.size,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "peak_bytes": int(getattr(mem, "peak_memory_in_bytes", 0) or 0),
            "alias_bytes": int(getattr(mem, "alias_size_in_bytes", 0)),
            "code_bytes": int(getattr(mem, "generated_code_size_in_bytes", 0)),
        },
        "cost": {k: float(v) for k, v in dict(cost).items()
                 if isinstance(v, (int, float))},
        "collectives": coll,
    }


def collective_bytes(compiled) -> dict:
    """Sum result-shape bytes of every collective op in the optimized HLO.

    (The result shape is the ring-traffic proxy: all-reduce result ==
    operand; all-gather result == total gathered bytes.)"""
    txt = compiled.as_text()
    out = {k: {"count": 0, "bytes": 0} for k in COLLECTIVE_OPS}
    shape_re = re.compile(
        r"=\s*(?:\([^)]*\)|((?:f|bf|s|u|pred)[0-9a-z]*)\[([0-9,]*)\][^ ]*)\s+"
        r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)")
    tuple_re = re.compile(r"((?:f|bf|s|u|pred)[0-9a-z]*)\[([0-9,]*)\]")
    for line in txt.splitlines():
        m = shape_re.search(line)
        if not m:
            continue
        op = m.group(3)
        if m.group(1):  # single result
            entries = [(m.group(1), m.group(2))]
        else:  # tuple result: parse all shapes in the tuple
            head = line.split("=")[1].split(op)[0]
            entries = tuple_re.findall(head)
        nbytes = 0
        for dt, dims in entries:
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * DTYPE_BYTES.get(dt, 4)
        out[op]["count"] += 1
        out[op]["bytes"] += nbytes
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items()
                             if isinstance(v, dict))
    return out


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             force: bool = False) -> dict:
    cfg = get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    out_path = RESULTS_DIR / f"{arch}__{shape_name}__{mesh_name}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())
    if shape not in shapes_for(cfg):
        result = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                  "status": "skipped",
                  "reason": "long_500k requires sub-quadratic attention "
                            "(DESIGN.md §4 skip list)"}
        out_path.write_text(json.dumps(result, indent=2))
        return result
    mesh = make_production_mesh(multi_pod=multi_pod)
    try:
        result = lower_cell(cfg, shape, mesh, mesh_name)
        result["status"] = "ok"
    except Exception as e:  # record failures as bugs to fix
        result = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                  "status": "error", "error": f"{type(e).__name__}: {e}",
                  "traceback": traceback.format_exc()[-3000:]}
    out_path.write_text(json.dumps(result, indent=2))
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    meshes = []
    if args.multi_pod or not args.single_pod:
        meshes.append(True)
    if args.single_pod or not args.multi_pod:
        meshes.append(False)
    meshes = sorted(set(meshes))  # [False, True] order: single first

    cells = []
    if args.all:
        for name, cfg in ASSIGNED.items():
            for sh in shapes_for(cfg):
                cells.append((name, sh.name))
    else:
        assert args.arch and args.shape, "--arch and --shape or --all"
        cells.append((args.arch, args.shape))

    for arch, shape in cells:
        for mp in meshes:
            r = run_cell(arch, shape, mp, force=args.force)
            status = r.get("status")
            extra = ""
            if status == "ok":
                flops = r["cost"].get("flops", 0)
                extra = (f"compile={r['compile_s']}s flops={flops:.3e} "
                         f"coll={r['collectives']['total_bytes']:.3e}B "
                         f"temp={r['memory']['temp_bytes']/2**30:.2f}GiB")
            else:
                extra = r.get("error", "")[:160]
            print(f"[{time.strftime('%H:%M:%S')}] {arch} {shape} "
                  f"{'2pod' if mp else '1pod'}: {status} {extra}",
                  flush=True)


if __name__ == "__main__":
    main()

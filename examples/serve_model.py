"""End-to-end driver: serve a real (reduced-config) model with batched
requests through the JAX serving engine — continuous batching, slot KV
cache, greedy decode.

    PYTHONPATH=src python examples/serve_model.py --arch qwen2.5-3b
"""
import argparse
import time

import jax
import numpy as np

from repro.configs.registry import get_config, reduced_config
from repro.launch.mesh import use_compile_cache
from repro.models import build_model
from repro.serving.engine import ServeRequest, ServingEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    args = ap.parse_args()

    use_compile_cache()
    cfg = reduced_config(get_config(args.arch))
    bundle = build_model(cfg)
    params = bundle.init(jax.random.key(0))
    engine = ServingEngine(bundle, params, slots=args.slots, max_len=128)

    rng = np.random.default_rng(0)
    t0 = time.time()
    for i in range(args.requests):
        prompt = rng.integers(0, cfg.vocab_size,
                              size=int(rng.integers(8, 24))).astype(np.int32)
        engine.submit(ServeRequest(i, prompt, max_new_tokens=args.max_new))
    done = engine.run_to_completion()
    dt = time.time() - t0
    total_tokens = sum(len(r.generated) for r in done)
    print(f"arch={cfg.name} (reduced) served {len(done)} requests, "
          f"{total_tokens} tokens in {dt:.1f}s "
          f"({total_tokens/dt:.1f} tok/s, "
          f"{engine.stats['decode_steps']} engine iterations)")
    for r in done[:3]:
        print(f"  req {r.req_id}: prompt[{len(r.prompt)}] -> "
              f"{r.generated[:8]}...")


if __name__ == "__main__":
    main()

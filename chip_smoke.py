"""Serve Qwen2.5-3B at its published width on one TPU chip, and check it.

Drives ``ServingEngine`` the way ``examples/serve_model.py`` does, but at the
full configuration (36 layers, d_model 2,048, vocabulary 151,936) with
seeded random weights, 8 slots x 4,096 tokens of KV cache.  Twelve seeded
requests: four share a 1,024-token system prefix, each with its own 64-token
suffix (the agent-loop shape Scepsy serves, taking the prefix-copy path),
and eight have distinct prompts of 512, 1,024 or 2,048 tokens.  Each asks
for 32 new tokens.  Then the logits of every generated position are
recomputed with the model's own prefill over prompt + generated tokens.

    python chip_smoke.py

Exits non-zero without the final line when JAX finds no TPU, when a request
is incomplete, no prompt token was served from the prefix cache, or a token
fails the check, and when any phase raises.  A passing run ends with
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
Everything runs in this one process, which holds the chip.
"""
from __future__ import annotations

import json
import sys
import time
from dataclasses import dataclass
from pathlib import Path
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.configs.base import ArchConfig  # noqa: E402
from repro.configs.registry import get_config  # noqa: E402
from repro.launch.mesh import use_compile_cache  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.models.transformer import ModelBundle  # noqa: E402
from repro.serving.engine import ServeRequest, ServingEngine  # noqa: E402

MODEL = "qwen2.5-3b"
SLOTS = 8
MAX_LEN = 4096
REF_BATCH = 8  # reference rows per prefill call; one compiled shape

# A token that is not the reference's argmax still passes when its reference
# logit is within NEAR_TIE_REL x |top logit| of the top.  The engine (one
# decode step per token against a bf16 KV cache) and the reference (one
# prefill over the whole sequence) round bf16 activations at different
# points, and random weights put the top logits of a 151,936-entry
# vocabulary close together.  A wrong token (stale KV, wrong position) sits
# far below the top: its logit is a random draw, not a near-maximum.
NEAR_TIE_REL = 2.0 ** -5


@dataclass(frozen=True)
class Traffic:
    """Seeded requests: an agent loop's shared system prefix, plus distinct
    prompts from a few lengths so that prefill compiles stay few."""

    prefix_len: int = 1024
    suffix_len: int = 64
    n_shared: int = 4
    lengths: Tuple[int, ...] = (512, 1024, 2048)
    n_distinct: int = 8
    new_tokens: int = 32

    def requests(self, vocab: int, seed: int) -> List[ServeRequest]:
        rng = np.random.default_rng(seed)

        def draw(n):
            return rng.integers(0, vocab, size=n, dtype=np.int32)

        prefix = draw(self.prefix_len)
        prompts = [np.concatenate([prefix, draw(self.suffix_len)])
                   for _ in range(self.n_shared)]
        prompts += [draw(self.lengths[i % len(self.lengths)])
                    for i in range(self.n_distinct)]
        return [ServeRequest(i, p, max_new_tokens=self.new_tokens)
                for i, p in enumerate(prompts)]


def check_tokens(bundle: ModelBundle, params, requests: List[ServeRequest],
                 batch: int = REF_BATCH) -> dict:
    """Recompute the logits behind every generated token with
    ``bundle.prefill`` over prompt + the tokens generated before it.

    Each (request, position) is one row, padded to one width and cut by
    ``lengths``, so the reference compiles once.  Returns counts of exact
    argmax matches, near-ties within ``NEAR_TIE_REL`` and bad tokens.
    """
    rows = [(r, j) for r in requests for j in range(len(r.generated))]
    width = max(len(r.prompt) + len(r.generated) - 1 for r in requests)
    width = -(-width // 128) * 128
    n = -(-len(rows) // batch) * batch
    tokens = np.zeros((n, width), np.int32)
    lengths = np.ones(n, np.int32)
    engine_tok = np.zeros(n, np.int32)
    for i, (r, j) in enumerate(rows):
        seq = np.concatenate([r.prompt, np.asarray(r.generated[:j], np.int32)])
        tokens[i, :len(seq)] = seq
        lengths[i] = len(seq)
        engine_tok[i] = r.generated[j]

    @jax.jit
    def reference(params, tokens, lengths, engine_tok):
        logits, _ = bundle.prefill(params, {"tokens": tokens,
                                            "lengths": lengths})
        mine = jnp.take_along_axis(logits, engine_tok[:, None], axis=-1)
        return jnp.argmax(logits, -1), jnp.max(logits, -1), mine[:, 0]

    top_tok, top, mine = [], [], []
    for lo in range(0, n, batch):
        sl = slice(lo, lo + batch)
        out = jax.device_get(reference(params, tokens[sl], lengths[sl],
                                       engine_tok[sl]))
        top_tok.append(out[0])
        top.append(out[1])
        mine.append(out[2])
    k = len(rows)
    top_tok = np.concatenate(top_tok)[:k]
    top = np.concatenate(top).astype(np.float64)[:k]
    gap = top - np.concatenate(mine).astype(np.float64)[:k]
    exact = top_tok == engine_tok[:k]
    near = ~exact & (gap <= NEAR_TIE_REL * np.abs(top))
    rel = gap / np.maximum(np.abs(top), 1e-30)
    return {"tokens": k, "exact": int(exact.sum()), "near_ties": int(near.sum()),
            "bad": int((~exact & ~near).sum()),
            "max_rel_gap": float(rel[~exact].max()) if (~exact).any() else 0.0}


def run(cfg: ArchConfig, traffic: Traffic = Traffic(), *, seed: int = 0,
        slots: int = SLOTS, max_len: int = MAX_LEN) -> dict:
    """Build the model and engine, serve ``traffic`` to completion, then
    check every generated token.  Returns what the run counted."""
    t0 = time.perf_counter()
    bundle = build_model(cfg)
    params = jax.block_until_ready(bundle.init(jax.random.key(seed)))
    engine = ServingEngine(bundle, params, slots=slots, max_len=max_len)
    jax.block_until_ready(engine.cache)
    setup_s = time.perf_counter() - t0

    requests = traffic.requests(cfg.vocab_size, seed)
    for r in requests:
        engine.submit(r)
    t0 = time.perf_counter()
    done = engine.run_to_completion()
    serve_s = time.perf_counter() - t0
    stats = dict(engine.stats)
    del engine  # frees the serving KV cache before the reference runs

    t0 = time.perf_counter()
    check = check_tokens(bundle, params, done)
    return {"setup_s": setup_s, "serve_s": serve_s,
            "check_s": time.perf_counter() - t0,
            "submitted": len(requests), "completed": len(done),
            "full_length": sum(len(r.generated) == traffic.new_tokens
                               for r in done),
            "full_prefills": sum(r.cached_tokens == 0 for r in done),
            "prefix_hits": sum(r.cached_tokens > 0 for r in done),
            **stats, "check": check, "requests": done}


def problems(result: dict) -> List[str]:
    """Why a run does not pass; empty when it does."""
    out = []
    if result["completed"] != result["submitted"]:
        out.append(f"{result['completed']}/{result['submitted']} completed")
    if result["full_length"] != result["submitted"]:
        out.append(f"{result['full_length']} requests got their full "
                   "token count")
    if result["cached_tokens"] <= 0:
        out.append("no prompt token was served from the prefix cache")
    if result["check"]["bad"]:
        out.append(f"{result['check']['bad']} tokens fail the check")
    return out


class CompileMeter:
    """While open, sums JAX's backend compile seconds (a persistent-cache
    hit counts only its retrieval) and counts persistent-cache hits."""

    def __enter__(self):
        self.seconds, self.cache_hits = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def main() -> int:
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    print(f"device: {dev.device_kind} x{len(devices)}")
    use_compile_cache()
    print(f"compile cache: {jax.config.jax_compilation_cache_dir}")
    cfg = get_config(MODEL)
    with CompileMeter() as meter:
        result = run(cfg)
    check = result["check"]
    print(f"model: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
          f"vocab={cfg.vocab_size} slots={SLOTS} max_len={MAX_LEN}")
    print(f"setup_s: {result['setup_s']:.3f}")
    print(f"compile_s: {meter.seconds:.3f} "
          f"(persistent cache hits: {meter.cache_hits})")
    print(f"serve_s: {result['serve_s']:.3f} (includes first-call compiles)")
    print(f"requests: {result['completed']}/{result['submitted']} completed, "
          f"{result['full_length']} with all "
          f"{result['requests'][0].max_new_tokens} tokens")
    print(f"prefills: {result['full_prefills']} full, "
          f"{result['prefix_hits']} from the prefix cache; "
          f"prefill_tokens: {result['prefill_tokens']}, "
          f"cached_tokens: {result['cached_tokens']}, "
          f"decode_steps: {result['decode_steps']}")
    print(f"check: {check['tokens']} tokens, {check['exact']} argmax, "
          f"{check['near_ties']} near-ties (within {NEAR_TIE_REL} x |top|), "
          f"{check['bad']} bad, max relative gap {check['max_rel_gap']:.6f}; "
          f"check_s: {result['check_s']:.3f}")
    mem = dev.memory_stats() or {}
    print(f"peak_bytes_in_use: {mem.get('peak_bytes_in_use')} "
          f"of bytes_limit: {mem.get('bytes_limit')}")
    errors = problems(result)
    if errors:
        print("chip_smoke FAILED: " + "; ".join(errors), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

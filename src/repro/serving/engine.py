"""The JAX serving engine: the on-chip path of this repository.

``ServingEngine`` runs a :class:`ModelBundle` from ``repro.models`` on
the default JAX device — a TPU chip in deployment (``chip_smoke.py`` serves
Qwen2.5-3B at full width through it on one v5e), the CPU in tests.
Slot-based continuous batching:

  * prefill admits a waiting request into a free slot (logits for its
    last token seed decoding); exact-prefix cache reuse via
    :class:`PrefixCache` + :meth:`SlotKVCache.copy_prefix` — the longest
    cached prefix of the prompt is *copied* from the slot that already
    holds its KV and only the suffix is computed (dense-KV models);
  * decode runs one jitted step for ALL active slots with per-slot
    positions (ragged continuous batching — the (B,) position path of
    ``attention_block_decode``);
  * greedy sampling; requests complete at EOS-budget exhaustion.

Everything Scepsy plans (trace, aggregate, profile, schedule, place) runs
on the host and prices engines with ``serving/costmodel.py``; the fleet-
scale behaviour is the discrete-event simulator.

Tracing: the engine's phases are ``jax.profiler.TraceAnnotation`` spans
named ``engine.*`` (inert unless a profiler session runs), so a profile
shows them on the device's clock beside the programs they launch; each
role has its own jitted program (``jit_prefill``, ``jit_batched_decode``,
``jit_suffix_decode``).  A request records when it was submitted and when
it left the queue (``submitted_at``, ``admitted_at``).  See
``docs/serving.md``, "Tracing the engine".
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation as span

from repro.models.transformer import ModelBundle
from repro.serving.kv_cache import SlotKVCache
from repro.serving.prefix_cache import PrefixCache


@dataclass
class ServeRequest:
    req_id: int
    prompt: np.ndarray  # (S,) int32
    max_new_tokens: int
    generated: List[int] = field(default_factory=list)
    slot: int = -1
    done: bool = False
    cached_tokens: int = 0  # prompt tokens served from the prefix cache
    # time.perf_counter() at submit() and when _admit took it off the queue
    submitted_at: Optional[float] = None
    admitted_at: Optional[float] = None


class ServingEngine:
    def __init__(self, bundle: ModelBundle, params, *, slots: int = 8,
                 max_len: int = 256, prefix_caching: bool = True,
                 min_prefix: int = 8):
        self.bundle = bundle
        self.params = params
        self.cfg = bundle.cfg
        self.slots = slots
        self.max_len = max_len
        self.min_prefix = min_prefix
        self.cache = bundle.init_cache(slots, max_len)
        # prefix reuse needs a positional (L, slots, KV, S, D) KV layout
        # (dense/MoE attention); recurrent-state caches (rwkv, hymba
        # groups) have no per-token prefix to copy.
        self._dense_kv = self._is_dense_kv(self.cache)
        self.prefix_cache = (PrefixCache()
                             if prefix_caching and self._dense_kv else None)
        self.lengths = np.zeros(slots, np.int32)
        self.active: Dict[int, ServeRequest] = {}  # slot -> request
        self.waiting: List[ServeRequest] = []
        self.free_slots = list(range(slots))
        self.stats = {"prefill_tokens": 0, "cached_tokens": 0,
                      "decode_steps": 0}

        # one program per role, so that a profile names what ran
        self._prefill = _jit("prefill", lambda params, tokens:
                             bundle.prefill(params, {"tokens": tokens}))
        self._decode = _jit("batched_decode", bundle.decode_step)
        self._suffix_decode = _jit("suffix_decode", bundle.decode_step)

    def _is_dense_kv(self, cache) -> bool:
        if not (isinstance(cache, tuple) and len(cache) == 2):
            return False
        k, v = cache
        return (hasattr(k, "ndim") and hasattr(v, "ndim")
                and k.ndim == 5 and v.ndim == 5
                and k.shape[1] == self.slots and k.shape[3] == self.max_len)

    def submit(self, req: ServeRequest) -> None:
        req.submitted_at = time.perf_counter()
        self.waiting.append(req)

    # -- engine iterations --
    def step(self) -> List[ServeRequest]:
        """One engine iteration; returns requests completed this step."""
        self._admit()
        return self._decode_step()

    def run_to_completion(self, max_steps: int = 10_000) -> List[ServeRequest]:
        out = []
        for _ in range(max_steps):
            out.extend(self.step())
            if not self.waiting and not self.active:
                break
        return out

    def _admit(self) -> None:
        while self.waiting and self.free_slots:
            req = self.waiting.pop(0)
            req.admitted_at = time.perf_counter()
            slot = self.free_slots.pop()
            req.slot = slot
            plen = len(req.prompt)
            with span("engine.admit", req_id=req.req_id, slot=slot,
                      prompt_len=plen) as admit:
                tokens = [int(t) for t in req.prompt]
                matched, src = 0, None
                if self.prefix_cache is not None:
                    with span("engine.prefix_index"):
                        matched, src = self.prefix_cache.longest_prefix(
                            tokens)
                        # the slot's old KV is about to be overwritten:
                        # every cache entry still pointing at it is stale
                        # from here on (the lookup above may legitimately
                        # have matched it — the bytes are still in place
                        # until we write)
                        self.prefix_cache.invalidate_slot(slot)
                    matched = min(matched, plen - 1)
                    if matched < self.min_prefix:
                        matched, src = 0, None
                admit.set_metadata(cached=matched)

                if src is not None:
                    first_tok = self._prefill_from_prefix(
                        req, slot, src, matched, tokens)
                    req.cached_tokens = matched
                    self.stats["cached_tokens"] += matched
                    self.stats["prefill_tokens"] += plen - matched
                else:
                    with span("engine.prefill", prompt_len=plen):
                        logits, cache = self._prefill(
                            self.params, jnp.asarray(req.prompt)[None])
                        # write the prefill cache into the slot (dense
                        # layouts)
                        self.cache = _merge_slot(self.cache, cache, slot,
                                                 plen, self.max_len)
                    self.stats["prefill_tokens"] += plen
                    with span("engine.sync"):
                        first_tok = int(jnp.argmax(logits[0]))
                self.lengths[slot] = plen
                if self.prefix_cache is not None:
                    with span("engine.prefix_index"):
                        self.prefix_cache.insert(tokens, slot)
                req.generated.append(first_tok)
                self.active[slot] = req

    def _prefill_from_prefix(self, req: ServeRequest, slot: int, src: int,
                             matched: int, tokens: List[int]) -> int:
        """Prefix-cache hit: copy the shared KV out of ``src`` and run
        only the suffix through the model (token-at-a-time decode on an
        isolated batch=1 view of the slot), returning the first sampled
        token."""
        with span("engine.prefix_copy", matched=matched):
            kv = SlotKVCache(k=self.cache[0], v=self.cache[1],
                             lengths=self.lengths)
            kv.copy_prefix(src, slot, matched)
            cache = (kv.k, kv.v)
            k1 = jax.lax.dynamic_slice_in_dim(cache[0], slot, 1, axis=1)
            v1 = jax.lax.dynamic_slice_in_dim(cache[1], slot, 1, axis=1)
        logits = None
        with span("engine.suffix", tokens=len(tokens) - matched):
            for pos in range(matched, len(tokens)):
                logits, (k1, v1) = self._suffix_decode(
                    self.params, (k1, v1),
                    jnp.asarray([tokens[pos]], jnp.int32),
                    jnp.asarray([pos], jnp.int32))
            self.cache = (
                jax.lax.dynamic_update_slice(cache[0], k1,
                                             (0, slot, 0, 0, 0)),
                jax.lax.dynamic_update_slice(cache[1], v1,
                                             (0, slot, 0, 0, 0)))
        with span("engine.sync"):
            return int(jnp.argmax(logits[0]))

    def _decode_step(self) -> List[ServeRequest]:
        if not self.active:
            return []
        slots = sorted(self.active)
        with span("engine.decode", active=len(slots)):
            with span("engine.dispatch"):
                tokens = np.zeros(self.slots, np.int32)
                for s in slots:
                    tokens[s] = self.active[s].generated[-1]
                pos = jnp.asarray(self.lengths, jnp.int32)
                logits, self.cache = self._decode(
                    self.params, self.cache, jnp.asarray(tokens), pos)
            self.stats["decode_steps"] += 1
            with span("engine.sync"):
                toks = np.asarray(jnp.argmax(logits, axis=-1))
            with span("engine.bookkeep"):
                completed = []
                for s in slots:
                    req = self.active[s]
                    self.lengths[s] += 1
                    req.generated.append(int(toks[s]))
                    if (len(req.generated) >= req.max_new_tokens
                            or self.lengths[s] >= self.max_len - 1):
                        req.done = True
                        completed.append(req)
                        del self.active[s]
                        # the slot's KV (prompt + all but the final
                        # generated token) stays valid until the slot is
                        # reused; register the full sequence for
                        # exact-prefix reuse
                        if self.prefix_cache is not None:
                            seq = ([int(t) for t in req.prompt]
                                   + req.generated[:-1])
                            with span("engine.prefix_index"):
                                self.prefix_cache.insert(
                                    seq[:self.max_len - 1], s)
                        self.lengths[s] = 0
                        self.free_slots.append(s)
        return completed


def _jit(name: str, fn):
    """``fn`` jitted as a program the profiler names ``jit_<name>``."""
    def program(*args):
        return fn(*args)
    program.__name__ = program.__qualname__ = name
    return jax.jit(program)


def _merge_slot(cache, prefill_cache, slot: int, plen: int, max_len: int):
    """Insert one sequence's prefill cache (batch=1) into slot ``slot``.

    Works structurally: any leaf with a batch dim of 1 at the engine's
    slot axis gets written.  Dense caches are (L, B, KV, S, D); rwkv
    states are (L, B, ...); hymba groups are nested dicts/tuples.
    """

    def merge(big, small):
        if big.ndim >= 2 and small.shape[0] == big.shape[0] \
                and small.shape[1] == 1:
            # (L, 1, ...) -> write into (L, slots, ...) at [*, slot]
            if big.ndim >= 4 and small.ndim == big.ndim \
                    and small.shape[-2] != big.shape[-2]:
                # seq axis shorter in prefill: pad to max_len
                pad = [(0, 0)] * small.ndim
                pad[-2] = (0, big.shape[-2] - small.shape[-2])
                small = jnp.pad(small, pad)
            return jax.lax.dynamic_update_slice(
                big, small.astype(big.dtype),
                (0, slot) + (0,) * (big.ndim - 2))
        if small.shape[0] == 1 and big.ndim == small.ndim:
            # (1, ...) leaves without layer dim (hymba singleton layers)
            if big.ndim >= 3 and small.shape[-2] != big.shape[-2]:
                pad = [(0, 0)] * small.ndim
                pad[-2] = (0, big.shape[-2] - small.shape[-2])
                small = jnp.pad(small, pad)
            return jax.lax.dynamic_update_slice(
                big, small.astype(big.dtype), (slot,) + (0,) * (big.ndim - 1))
        raise ValueError(f"cannot merge {small.shape} into {big.shape}")

    return jax.tree.map(merge, cache, prefill_cache)

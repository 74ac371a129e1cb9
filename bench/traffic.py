"""The one traffic generator: reads a mix's parameters from
``bench/traffic/<mix>.json`` and turns them into closed-loop workflows.

Two things are drawn apart, from two seeds:

* the *length script*, from the mix's own ``shape_seed``: how many chunks
  each workflow has, and every prompt and output length.  It is the same
  in every run of a cell, so every run carries the same work;
* the *contents*, from ``--seed``: token ids only.  Workflow slot i runs
  the scripts i, i + n, i + 2n, ... of the pool (n slots) in every run: a
  window holds only part of the pool, so an order drawn from the seed
  would change the work a run does (it moved the median time to first
  token of a map cell by 2.5x between seeds).

A workflow is a generator.  It yields ``("calls", [(prompt, max_new, kind),
...])`` and is sent back the generated tokens of each call once all of them
are finished.  Reduce prompts are built from the tokens the engine really
generated.

The mix (``"kind": "fan_out"``) is map-reduce summarisation, with lengths
as ``workflows/map_reduce.py`` draws them: every chunk of a document is
summarised at once, then fan-in trees fold the summaries; a reduce prompt
is an instruction followed by the first ``reduce_item`` tokens of each
summary it folds.
"""
from __future__ import annotations

import json
import math
import random
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load_mix(name: str) -> dict:
    path = TRAFFIC_DIR / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    return json.loads(path.read_text())


def seed_words(seed: int, n: int = 2) -> np.ndarray:
    """``n`` uint32 words from a seed of any size (a JAX key takes only 32
    bits of a Python int, so every seed goes through here first)."""
    return np.random.SeedSequence(int(seed)).generate_state(n, np.uint32)


def host_rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(w) for w in seed_words(seed)]
                                 + [int(s) for s in stream])


def _draw(rng: random.Random, spec: dict) -> float:
    """base + exp(mean) and/or + lognormal(mu, sigma), as the workflows draw
    them: each random part is truncated to a whole number on its own."""
    value = spec.get("base", 0)
    if "exp_mean" in spec:
        value += int(rng.expovariate(1.0 / spec["exp_mean"]))
    if "lognormal" in spec:
        mu, sigma = spec["lognormal"]
        value += int(rng.lognormvariate(mu, sigma))
    return value


# ---------------------------------------------------------------------------
# length scripts
# ---------------------------------------------------------------------------


def _fan_out_script(rng: random.Random, mix: dict) -> dict:
    doc = _draw(rng, mix["document"])
    chunks = min(max(math.ceil(doc / mix["chunk_tokens"]),
                     mix["chunks"]["min"]), mix["chunks"]["max"])
    spec = mix["map_prompt"]
    maps = []
    for _ in range(chunks):
        p = _draw(rng, spec)
        p = min(-(-p // spec["multiple"]) * spec["multiple"], spec["cap"])
        maps.append([p, _draw(rng, mix["map_output"])])
    levels, width, fan = [], chunks, mix["reduce_fan_in"]
    while width > 1:
        groups = math.ceil(width / fan)
        out = (mix["reduce_output"] if groups > 1
               else _draw(rng, mix["final_output"]))
        levels.append([[min(width - g * fan, fan), out]
                       for g in range(groups)])
        width = groups
    return {"maps": maps, "reduces": levels}


def length_scripts(mix: dict) -> List[dict]:
    if mix["kind"] != "fan_out":
        raise ValueError(f"no generator for a mix of kind {mix['kind']!r}")
    rng = random.Random(mix["shape_seed"])
    return [_fan_out_script(rng, mix) for _ in range(mix["scripts"])]


def _reduce_prompt(mix: dict, k: int) -> int:
    return k * mix["reduce_item"] + mix["reduce_instruction"]


def full_prefill_lengths(mix: dict) -> List[int]:
    """Prompt lengths that reach the engine as a full prefill: all of them,
    since no two prompts share a prefix."""
    out = set()
    for s in length_scripts(mix):
        out.update(p for p, _ in s["maps"])
        out.update(_reduce_prompt(mix, k) for level in s["reduces"]
                   for k, _ in level)
    return sorted(out)


# ---------------------------------------------------------------------------
# workflows with contents
# ---------------------------------------------------------------------------


@dataclass
class Traffic:
    mix: dict
    vocab: int
    seed: int

    def __post_init__(self):
        self.scripts = length_scripts(self.mix)
        self.started: Dict[int, int] = {}

    def _tokens(self, rng: np.random.Generator, n: int) -> np.ndarray:
        return rng.integers(0, self.vocab, size=n, dtype=np.int32)

    def next_workflow(self, slot: int):
        """The next workflow of closed-loop slot ``slot``: slot i runs the
        scripts i, i + n, ... of the pool, cycling."""
        k = self.started.get(slot, 0)
        self.started[slot] = k + 1
        n = self.mix["workflows"]
        script = self.scripts[(slot + k * n) % len(self.scripts)]
        return self._fan_out(script, host_rng(self.seed, 1, slot, k))

    def _fan_out(self, s: dict, rng: np.random.Generator):
        mix = self.mix
        outs = yield ("calls", [(self._tokens(rng, p), out, "map")
                                for p, out in s["maps"]])
        item = mix["reduce_item"]
        for level in s["reduces"]:
            calls, i = [], 0
            for k, out in level:
                parts = [self._tokens(rng, mix["reduce_instruction"])]
                parts += [o[:item] for o in outs[i:i + k]]
                i += k
                calls.append((np.concatenate(parts), out, "reduce"))
            outs = yield ("calls", calls)

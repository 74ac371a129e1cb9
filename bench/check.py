"""What decides ``correct``: the served tokens against the plain reference.

Once the window has closed and the engine is freed, a sample of the calls
finished in the window, drawn from the seed and holding the longest of them,
is run through the configuration's reference once each: prompt and served
tokens together, one forward pass.  At every served position the number
read is the gap by which the served token's reference logit lies below the
reference's best logit there (0 where the engine served the reference's
argmax).  Two numbers are read from those gaps: the widest, and the mean.
A cell's limits file (``bench/limits/<cell>.json``) gives a limit to each
number that separates the program's readings from its control's; only
those are compared.

``control_gaps`` reads, for the reference computed in a lower precision
(``control`` of the reference module: the step a later change might be
tempted by), at each position the gap of the token that the lower
precision puts first.
"""
from __future__ import annotations

import importlib
from functools import partial
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.traffic import host_rng

NUMBERS = ("max_logit_gap", "mean_logit_gap")
SAMPLE_TOKENS = 300  # served tokens to compare, at least
SAMPLE_CALLS = 8  # calls in the sample, at most
BUCKET = 1024  # the reference's sequences are padded to a multiple of this
BLOCK = 256  # served positions scored per logits call


def reference_module(cfg: dict):
    return importlib.import_module(f"bench.references.{cfg['reference']}")


def sample_calls(calls: Sequence, seed: int) -> List:
    """The longest finished call, then others in an order drawn from the
    seed, until the sample holds SAMPLE_TOKENS served tokens."""
    if not calls:
        return []
    calls = sorted(calls, key=lambda c: (len(c.prompt) + len(c.tokens),
                                         c.index))
    picked = [calls.pop()]
    order = host_rng(seed, 2).permutation(len(calls))
    for i in order:
        if (sum(len(c.tokens) for c in picked) >= SAMPLE_TOKENS
                or len(picked) >= SAMPLE_CALLS):
            break
        picked.append(calls[i])
    return picked


class Reference:
    """The configuration's reference, jitted once per padded length."""

    def __init__(self, cfg: dict, params):
        ref = reference_module(cfg)
        self.cfg, self.params = cfg, params
        self.hidden = jax.jit(partial(ref.hidden_states, cfg=cfg),
                              static_argnames="control")

        def gaps(params, hidden, pos, served, control_hidden=None,
                 control=None):
            logits = ref.logits_at(params, hidden, pos, cfg)
            best = jnp.max(logits, axis=-1)
            if control_hidden is not None:
                served = jnp.argmax(ref.logits_at(params, control_hidden, pos,
                                                  cfg, control), axis=-1)
            mine = jnp.take_along_axis(logits, served[:, None], axis=-1)
            return best - mine[:, 0]

        self._gaps = jax.jit(gaps, static_argnames="control")

    def _rows(self, call):
        seq = np.concatenate([call.prompt, np.asarray(call.tokens[:-1],
                                                      np.int32)])
        width = -(-len(seq) // BUCKET) * BUCKET
        tokens = np.zeros(width, np.int32)
        tokens[:len(seq)] = seq
        # the logits at position p predict token p + 1: served token j
        # follows position len(prompt) - 1 + j
        pos = len(call.prompt) - 1 + np.arange(len(call.tokens))
        return tokens, pos, np.asarray(call.tokens, np.int32)

    def _blocks(self, pos, served):
        for lo in range(0, len(pos), BLOCK):
            n = min(BLOCK, len(pos) - lo)
            p = np.zeros(BLOCK, np.int32)
            s = np.zeros(BLOCK, np.int32)
            p[:n], s[:n] = pos[lo:lo + n], served[lo:lo + n]
            yield n, p, s

    def served_gaps(self, calls) -> np.ndarray:
        """Per served token, the reference's best logit minus its logit
        for the served token."""
        out = []
        for call in calls:
            tokens, pos, served = self._rows(call)
            hidden = self.hidden(self.params, tokens)
            for n, p, s in self._blocks(pos, served):
                gap = self._gaps(self.params, hidden, p, s)
                out.append(np.asarray(gap[:n], np.float64))
        return np.concatenate(out) if out else np.zeros(0)

    def control_gaps(self, calls, control: str) -> np.ndarray:
        """Per served position, the gap of the token that the reference
        computed under ``control`` puts first."""
        out = []
        for call in calls:
            tokens, pos, served = self._rows(call)
            hidden = self.hidden(self.params, tokens)
            c_hidden = self.hidden(self.params, tokens, control=control)
            for n, p, s in self._blocks(pos, served):
                gap = self._gaps(self.params, hidden, p, s, c_hidden,
                                 control=control)
                out.append(np.asarray(gap[:n], np.float64))
        return np.concatenate(out) if out else np.zeros(0)


def readings(gaps: np.ndarray) -> dict:
    if not len(gaps):
        return {}
    return {"max_logit_gap": float(gaps.max()),
            "mean_logit_gap": float(gaps.mean())}


def compare(gaps: np.ndarray, limits: dict) -> dict:
    """The verdict (``ok``) and each number compared beside its limit."""
    read = readings(gaps)
    out = {"tokens_compared": {"value": int(len(gaps)),
                               "limit": "at least 1"}}
    ok = bool(read)
    for name, lim in limits.items():
        value = read.get(name)
        out[name] = {"value": value, "limit": lim}
        ok = ok and value <= lim
    return {"ok": ok, **out}

"""The closed-loop workflow driver: the window's only client of the engine.

Each workflow slot runs one workflow at a time (``Traffic.next_workflow``)
and waits for every reply before its next step, as an agent program does;
a finished workflow is replaced at once by the slot's next script.  The
window drives the program only through ``ServingEngine.submit`` and
``ServingEngine.step``.

The engine's two phases are wrapped on the instance, so that the
benchmark's spans ``bench.admit`` and ``bench.decode`` enclose them and the
first token of a call is stamped when its admission returns (it reaches
the client before the step's decode).  Tokens made by the step's decode
are stamped when the step returns.  Everything the per-layer metrics count
is counted here, around those calls, each count stamped with the host time
it was made at (``Tally``), so that a traced run can sum them over the part
of the window that its trace holds.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field, fields
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np
from repro.serving.engine import ServeRequest

from bench import flops


@dataclass
class Call:
    index: int
    slot: int  # the workflow slot that made it
    kind: str
    prompt: np.ndarray
    max_new: int
    submitted: float
    times: List[float] = field(default_factory=list)
    req: object = None
    done_at: Optional[float] = None

    @property
    def tokens(self) -> List[int]:
        return self.req.generated


@dataclass
class Counters:
    """Counts over a span of the window (``Tally.over``)."""

    decode_steps: int = 0
    decoded_slots: int = 0  # active slots summed over decode steps
    decode_bytes: float = 0.0  # least bytes of those steps (flops.py)
    flops: float = 0.0  # every token computed (flops.py)
    prefill_flops: float = 0.0  # full prefills only
    full_prefills: int = 0
    prompt_tokens: int = 0  # of calls admitted in the window
    cached_tokens: int = 0
    compiles: int = 0


class Tally:
    """Counts, each stamped with the host time it was made at."""

    def __init__(self):
        self.rows: List[Tuple[float, dict]] = []

    def add(self, t: float, **counts) -> None:
        self.rows.append((t, counts))

    def over(self, lo: float, hi: float) -> Counters:
        total = {f.name: 0 for f in fields(Counters)}
        for t, counts in self.rows:
            if lo <= t <= hi:
                for k, v in counts.items():
                    total[k] += v
        return Counters(**total)


def span(name: str):
    return jax.profiler.TraceAnnotation(name)


class Driver:
    def __init__(self, engine, traffic, cfg: dict,
                 clock=time.perf_counter):
        self.engine, self.traffic, self.cfg = engine, traffic, cfg
        self.clock = clock
        self.calls: List[Call] = []
        self.inflight: Dict[int, Call] = {}  # req_id -> call
        self.batches: Dict[int, list] = {}  # workflow slot -> its calls
        self.workflows: Dict[int, object] = {}
        self.tally = Tally()
        self.window = (0.0, 0.0)
        self._decode_plan = (0, 0)  # (active slots, KV tokens) at decode
        self._wrap(engine)

    # -- the engine's phases, with spans and first-token stamps --
    def _wrap(self, engine):
        admit, decode = engine._admit, engine._decode_step

        def admitted():
            with span("bench.admit"):
                admit()
            now = self.clock()
            active = list(engine.active.values())
            for req in active:
                call = self.inflight.get(req.req_id)
                if call is not None and not call.times:
                    call.times.append(now)
                    self._count_admission(call, now)
            # each active slot attends to its prompt and every token so
            # far, the one this decode writes included
            self._decode_plan = (len(active), sum(
                len(r.prompt) + len(r.generated) for r in active))

        def decoded():
            with span("bench.decode"):
                return decode()

        engine._admit, engine._decode_step = admitted, decoded

    def _count_admission(self, call: Call, t: float) -> None:
        cfg, plen = self.cfg, len(call.prompt)
        cached = call.req.cached_tokens
        if cached == 0:
            f = flops.prefill_flops(cfg, plen)
            self.tally.add(t, prompt_tokens=plen, full_prefills=1,
                           prefill_flops=f, flops=f)
        else:  # the suffix, one batch-1 decode a token
            self.tally.add(t, prompt_tokens=plen, cached_tokens=cached,
                           flops=sum(flops.decode_flops(cfg, pos + 1)
                                     for pos in range(cached, plen)))

    # -- workflows --
    def _submit(self, slot: int, calls) -> None:
        batch = []
        for prompt, max_new, kind in calls:
            call = Call(len(self.calls), slot, kind,
                        np.asarray(prompt, np.int32), int(max_new),
                        self.clock())
            call.req = ServeRequest(call.index, call.prompt,
                                    max_new_tokens=call.max_new)
            self.calls.append(call)
            self.inflight[call.index] = call
            batch.append(call)
            self.engine.submit(call.req)
        self.batches[slot] = batch

    def _advance(self, slot: int, reply) -> None:
        """Send ``reply`` into the slot's workflow and submit the calls it
        yields next; a finished workflow is replaced by the next one."""
        while True:
            try:
                _, calls = self.workflows[slot].send(reply)
            except StopIteration:
                self.workflows[slot] = self.traffic.next_workflow(slot)
                reply = None
                continue
            self._submit(slot, calls)
            return

    def _finish(self, req, now: float) -> None:
        call = self.inflight.pop(req.req_id)
        call.done_at = now
        batch = self.batches[call.slot]
        if all(c.done_at is not None for c in batch):
            del self.batches[call.slot]
            self._advance(call.slot, [np.asarray(c.tokens, np.int32)
                                      for c in batch])

    # -- the window --
    def run(self, seconds: float) -> None:
        eng = self.engine
        t0 = self.clock()
        self.window = (t0, t0 + seconds)
        with span("bench.window"):
            for slot in range(self.traffic.mix["workflows"]):
                self.workflows[slot] = self.traffic.next_workflow(slot)
                self._advance(slot, None)
            while self.clock() < self.window[1]:
                steps = eng.stats["decode_steps"]
                done = eng.step()
                now = self.clock()
                with span("bench.driver"):
                    self._after_step(steps, done, now)

    def _after_step(self, steps_before: int, done, now: float) -> None:
        cfg = self.cfg
        if self.engine.stats["decode_steps"] > steps_before:
            active, kv_tokens = self._decode_plan
            f = 0.0
            for call in self.inflight.values():
                # the step decoded one token of each active call
                if len(call.req.generated) > len(call.times):
                    ctx = len(call.prompt) + len(call.req.generated) - 1
                    f += flops.decode_flops(cfg, ctx)
            self.tally.add(now, decode_steps=1, decoded_slots=active,
                           decode_bytes=flops.decode_step_bytes(cfg, kv_tokens),
                           flops=f)
        for call in self.inflight.values():
            new = len(call.req.generated) - len(call.times)
            if new > 0:
                call.times.extend([now] * new)
        for req in done:
            self._finish(req, now)

"""The traffic generator: lengths are a fixed script per mix, contents
come from the seed; the readers count what the window saw."""
from collections import Counter
from types import SimpleNamespace

import numpy as np
import pytest

from bench.driver import Tally
from bench.metrics import first_token_p50_ms, itl_p95_ms, out_tok_s
from bench.traffic import (Traffic, full_prefill_lengths, length_scripts,
                           load_mix)

VOCAB = 1000


def drive(mix, seed):
    """Every script of the pool once, with made-up replies: the calls'
    shapes, and their prompts."""
    traffic = Traffic(mix, VOCAB, seed)
    n = mix["workflows"]
    shapes, prompts = [], []
    for slot in range(n):
        for _ in range(len(traffic.scripts) // n):
            flow = traffic.next_workflow(slot)
            reply = None
            while True:
                try:
                    kind, arg = flow.send(reply)
                except StopIteration:
                    break
                reply = None
                if kind == "calls":
                    shapes += [(len(p), m, k) for p, m, k in arg]
                    prompts += [p for p, _, _ in arg]
                    reply = [np.full(m, 7, np.int32) for _, m, _ in arg]
    return Counter(shapes), prompts


@pytest.mark.parametrize("seed", [2, 2 ** 40 + 1])
def test_lengths_fixed_across_seeds_tokens_not(seed):
    mix = load_mix("map")
    assert length_scripts(mix) == length_scripts(load_mix("map"))
    shapes_a, prompts_a = drive(mix, 1)
    shapes_b, prompts_b = drive(mix, seed)
    assert shapes_a == shapes_b
    first_a = sorted(tuple(p[:16]) for p in prompts_a)
    first_b = sorted(tuple(p[:16]) for p in prompts_b)
    assert first_a != first_b
    a_again, _ = drive(mix, 1)
    assert a_again == shapes_a


def test_shapes_the_engine_compiles():
    lengths = full_prefill_lengths(load_mix("map"))
    maps = [n for n in lengths if n > 400]
    assert maps and all(n % 64 == 0 and n <= 1024 for n in maps)
    assert [n for n in lengths if n <= 400] == [130, 220, 310, 400]


def test_counts_sum_over_the_part_of_the_window_asked_for():
    # a traced run sums its counts only up to the end of what its trace
    # holds, which the profiler's buffer may put before the window's close
    tally = Tally()
    tally.add(9.0, full_prefills=1, prefill_flops=5.0, flops=5.0)
    tally.add(11.0, decode_steps=1, decoded_slots=3, decode_bytes=7.0)
    tally.add(16.0, decode_steps=1, decoded_slots=4, decode_bytes=7.0)
    tally.add(21.0, decode_steps=1, decoded_slots=8, decode_bytes=7.0)
    whole = tally.over(10.0, 20.0)
    assert (whole.decode_steps, whole.decoded_slots) == (2, 7)
    assert whole.full_prefills == 0 and whole.flops == 0
    cut = tally.over(10.0, 15.0)
    assert (cut.decode_steps, cut.decoded_slots, cut.decode_bytes) == \
        (1, 3, 7.0)


def test_end_to_end_readers_count_tokens_cut_by_the_window():
    calls = [SimpleNamespace(submitted=8.0, times=[9.0, 11.0, 15.0]),
             SimpleNamespace(submitted=10.5, times=[12.0, 19.0, 21.0]),
             SimpleNamespace(submitted=19.5, times=[])]
    run = SimpleNamespace(window=(10.0, 20.0), seconds=10.0, calls=calls)
    # 11, 15 of the first call (unfinished at 9 is before), 12 and 19 of
    # the second (21 is after the close): four tokens in ten seconds
    assert out_tok_s.read(run) == pytest.approx(0.4)
    # only the second call's first token fell inside the window
    assert first_token_p50_ms.read(run) == pytest.approx(1500.0)
    # gaps with both ends inside: 11->15 and 12->19
    assert itl_p95_ms.read(run) == pytest.approx(
        1e3 * np.percentile([4.0, 7.0], 95))

"""The engine's own instrumentation: ``engine.*`` host spans in the
profiler's trace, nested as the engine's phases nest; one jitted program
per role, each under its own name; each request's queue stamps.  Served
tokens do not depend on whether the profiler records."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config, reduced_config
from repro.models import build_model
from repro.serving.engine import ServeRequest, ServingEngine

# span -> the spans it may sit directly or indirectly inside (None: top)
PARENTS = {
    "engine.admit": None,
    "engine.decode": None,
    "engine.prefix_index": ("engine.admit", "engine.bookkeep"),
    "engine.prefill": ("engine.admit",),
    "engine.prefix_copy": ("engine.admit",),
    "engine.suffix": ("engine.admit",),
    "engine.dispatch": ("engine.decode",),
    "engine.sync": ("engine.admit", "engine.decode"),
    "engine.bookkeep": ("engine.decode",),
}


@pytest.fixture(scope="module")
def parts():
    cfg = reduced_config(get_config("qwen2.5-3b"))
    bundle = build_model(cfg)
    return cfg, bundle, bundle.init(jax.random.key(0))


def miss_then_hit(parts):
    """A prompt served cold (a miss), then the same prompt again (a
    prefix hit); returns the engine and the two requests."""
    cfg, bundle, params = parts
    prompt = np.random.default_rng(7).integers(
        0, cfg.vocab_size, size=24).astype(np.int32)
    eng = ServingEngine(bundle, params, slots=2, max_len=64)
    reqs = [ServeRequest(i, prompt, max_new_tokens=4) for i in range(2)]
    for req in reqs:
        eng.submit(req)
        eng.run_to_completion()
    return eng, reqs


def engine_spans(log_dir):
    """(name, start_ns, end_ns, stats) of every ``engine.*`` host event."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                {k: v for k, v in ev.stats}))
    return out


@pytest.fixture(scope="module")
def traced(parts, tmp_path_factory):
    log_dir = str(tmp_path_factory.mktemp("engine_trace"))
    with jax.profiler.trace(log_dir):
        eng, reqs = miss_then_hit(parts)
    return eng, reqs, engine_spans(log_dir)


def test_every_span_is_recorded_and_nests_as_the_engine_does(traced):
    eng, reqs, spans = traced
    assert reqs[1].cached_tokens == len(reqs[1].prompt) - 1
    assert {s[0] for s in spans} == set(PARENTS)
    for name, start, end, _ in spans:
        around = [p for p in spans if p[1] <= start and end <= p[2]
                  and p[0] != name]
        if PARENTS[name] is None:
            assert not around, (name, around)
        else:
            assert any(p[0] in PARENTS[name] for p in around), name
    admits = sorted((s for s in spans if s[0] == "engine.admit"),
                    key=lambda s: s[1])
    assert [s[3]["req_id"] for s in admits] == [0, 1]
    assert [s[3]["cached"] for s in admits] == [0, reqs[1].cached_tokens]
    assert {s[3]["slot"] for s in admits} == {r.slot for r in reqs}
    assert all(s[3]["prompt_len"] == 24 for s in admits)
    decodes = [s for s in spans if s[0] == "engine.decode"]
    assert len(decodes) == eng.stats["decode_steps"]
    assert all(s[3]["active"] == 1 for s in decodes)
    (suffix,) = [s for s in spans if s[0] == "engine.suffix"]
    assert suffix[3]["tokens"] == 1


def test_served_tokens_do_not_depend_on_the_profiler(parts, traced):
    _, traced_reqs, _ = traced
    _, reqs = miss_then_hit(parts)
    assert [r.generated for r in reqs] == \
        [r.generated for r in traced_reqs]
    assert reqs[1].generated == reqs[0].generated


def test_each_role_is_its_own_named_program(parts):
    cfg, bundle, params = parts
    eng = ServingEngine(bundle, params, slots=2, max_len=64)
    tokens = jnp.zeros((1, 16), jnp.int32)
    one = jnp.zeros((1,), jnp.int32)
    k1 = eng.cache[0][:, :1]
    v1 = eng.cache[1][:, :1]
    texts = {
        "jit_prefill": eng._prefill.lower(params, tokens),
        "jit_batched_decode": eng._decode.lower(
            params, eng.cache, jnp.zeros((2,), jnp.int32),
            jnp.zeros((2,), jnp.int32)),
        "jit_suffix_decode": eng._suffix_decode.lower(
            params, (k1, v1), one, one),
    }
    for name, lowered in texts.items():
        assert lowered.as_text().startswith(f"module @{name} "), name
    decodes = [n for n in texts if "decode" in n]
    assert len(decodes) == 2 and eng._decode is not eng._suffix_decode


def test_requests_record_their_wait_in_the_queue(parts):
    cfg, bundle, params = parts
    rng = np.random.default_rng(11)
    eng = ServingEngine(bundle, params, slots=1, max_len=64)
    reqs = [ServeRequest(i, rng.integers(0, cfg.vocab_size, size=12)
                         .astype(np.int32), max_new_tokens=3)
            for i in range(3)]
    for req in reqs:
        eng.submit(req)
    eng.run_to_completion()
    for req in reqs:
        assert req.submitted_at is not None
        assert req.admitted_at >= req.submitted_at
    # one slot: each request waits out the one before it
    assert reqs[0].admitted_at < reqs[1].admitted_at < reqs[2].admitted_at

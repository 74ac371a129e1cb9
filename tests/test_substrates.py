"""Tests for the training/serving substrates: checkpointing, data
pipeline, fault tolerance, gradient compression, KV caches, prefix cache,
and the real serving engine."""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config, reduced_config
from repro.distributed.compression import (compress_tree, dequantize_int8,
                                           init_error, quantize_int8)
from repro.distributed.fault_tolerance import FaultToleranceController
from repro.models import build_model
from repro.serving.engine import ServeRequest, ServingEngine
from repro.serving.kv_cache import PagedKVCache
from repro.serving.prefix_cache import PrefixCache
from repro.training import checkpoint as ckpt
from repro.training.data import DataConfig, SyntheticCorpus
from repro.training.optimizer import AdamWConfig, apply_updates, init_state


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------


def test_checkpoint_roundtrip_and_latest():
    cfg = reduced_config(get_config("qwen2.5-3b"))
    bundle = build_model(cfg)
    params = bundle.init(jax.random.key(0))
    opt = init_state(params)
    with tempfile.TemporaryDirectory() as d:
        assert ckpt.latest_step(d) is None
        ckpt.save_checkpoint(d, 10, (params, opt), extra={"arch": cfg.name})
        ckpt.save_checkpoint(d, 20, (params, opt))
        assert ckpt.latest_step(d) == 20
        (p2, o2), step, extra = ckpt.restore_checkpoint(d, (params, opt),
                                                        step=10)
        assert step == 10 and extra["arch"] == cfg.name
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        ckpt.prune_old(d, keep=1)
        assert ckpt.latest_step(d) == 20
        with pytest.raises(Exception):
            ckpt.restore_checkpoint(d, (params, opt), step=10)


def test_training_resumes_identically():
    """Train 4 steps == train 2, checkpoint, restore, train 2 more."""
    cfg = reduced_config(get_config("qwen2.5-3b"))
    bundle = build_model(cfg)
    data = SyntheticCorpus(DataConfig(cfg.vocab_size, 16, 4))
    opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=2)
    grad = jax.jit(jax.value_and_grad(bundle.loss_fn))

    def steps(params, opt, start, n):
        for s in range(start, start + n):
            batch = {k: jnp.asarray(v) for k, v in data.batch_at(s).items()}
            _, g = grad(params, batch)
            params, opt = apply_updates(params, g, opt, opt_cfg)
        return params, opt

    p0 = bundle.init(jax.random.key(0))
    pa, oa = steps(p0, init_state(p0), 0, 4)

    pb, ob = steps(p0, init_state(p0), 0, 2)
    with tempfile.TemporaryDirectory() as d:
        ckpt.save_checkpoint(d, 2, (pb, ob))
        (pb, ob), step, _ = ckpt.restore_checkpoint(d, (pb, ob))
        pb = jax.tree.map(jnp.asarray, pb)
        ob = jax.tree.map(jnp.asarray, ob)
    pb, ob = steps(pb, ob, 2, 2)
    for a, b in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-6)


def test_data_pipeline_deterministic_and_sharded():
    c = DataConfig(vocab_size=100, seq_len=8, global_batch=8)
    full = SyntheticCorpus(c)
    s0 = SyntheticCorpus(c, shard=0, num_shards=2)
    s1 = SyntheticCorpus(c, shard=1, num_shards=2)
    b = full.batch_at(3)
    assert b["tokens"].shape == (8, 8)
    np.testing.assert_array_equal(b["tokens"], full.batch_at(3)["tokens"])
    assert s0.batch_at(3)["tokens"].shape == (4, 8)
    assert not np.array_equal(s0.batch_at(3)["tokens"],
                              s1.batch_at(3)["tokens"])
    assert (b["labels"][:, :-1] == b["tokens"][:, 1:]).all()


# ---------------------------------------------------------------------------
# fault tolerance
# ---------------------------------------------------------------------------


def test_failure_detection_and_remesh():
    ftc = FaultToleranceController(8, grace=10.0, model_ways=16)
    for w in range(8):
        ftc.heartbeat(w, 0.0)
    assert ftc.check(5.0) is None
    for w in range(7):  # worker 7 goes silent
        ftc.heartbeat(w, 20.0)
    plan = ftc.check(28.0)  # 7 last seen at t=0 (>grace); others at t=20
    assert plan is not None
    assert plan.dropped_workers == (7,)
    assert plan.data_ways == 4  # largest pow2 <= 7
    assert plan.restart_from_checkpoint
    assert 7 not in ftc.alive_workers()


def test_straggler_detection():
    ftc = FaultToleranceController(4, straggler_factor=2.0, patience=2)
    for t in range(5):
        for w in range(4):
            ftc.heartbeat(w, float(t))
            ftc.report_step(w, 1.0 if w != 2 else 5.0)
        plan = ftc.check(float(t))
        if plan:
            assert 2 in plan.dropped_workers
            return
    pytest.fail("straggler never detected")


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------


def test_int8_quantization_bounded_error():
    x = jax.random.normal(jax.random.key(0), (1000,)) * 3.0
    q, s = quantize_int8(x)
    deq = dequantize_int8(q, s, x.shape)
    err = jnp.max(jnp.abs(deq - x))
    assert float(err) <= float(jnp.max(jnp.abs(x))) / 127.0 + 1e-6


def test_error_feedback_preserves_signal():
    grads = {"w": jax.random.normal(jax.random.key(1), (64, 64))}
    err = init_error(grads)
    total_sent = jnp.zeros((64, 64))
    total_true = jnp.zeros((64, 64))
    for i in range(20):
        g = {"w": jax.random.normal(jax.random.key(i + 2), (64, 64)) * 0.1}
        total_true = total_true + g["w"]
        sent, err = compress_tree(g, err)
        total_sent = total_sent + sent["w"]
    # accumulated compressed sum tracks the true sum (error feedback)
    resid = float(jnp.max(jnp.abs(total_sent + err["w"] - total_true)))
    assert resid < 1e-3


# ---------------------------------------------------------------------------
# KV caches
# ---------------------------------------------------------------------------


def test_paged_cache_matches_contiguous():
    L, KV, D, ps = 2, 2, 8, 4
    cache = PagedKVCache.create(L, num_pages=16, kv_heads=KV, page_size=ps,
                                head_dim=D, dtype=jnp.float32)
    rng = jax.random.key(0)
    k_all = jax.random.normal(rng, (L, KV, 10, D))
    v_all = k_all * 2
    cache.alloc_seq(7)
    cache.append(7, k_all[:, :, :6], v_all[:, :, :6])
    cache.append(7, k_all[:, :, 6:], v_all[:, :, 6:])
    k, v, length = cache.gather_seq(7)
    assert length == 10
    np.testing.assert_allclose(np.asarray(k[:, :, :10]), np.asarray(k_all),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(v[:, :, :10]), np.asarray(v_all),
                               rtol=1e-6)
    cache.free_seq(7)
    assert len(cache.free_pages) == 16


def test_paged_cache_exhaustion():
    cache = PagedKVCache.create(1, num_pages=2, kv_heads=1, page_size=2,
                                head_dim=4)
    cache.alloc_seq(0)
    k = jnp.zeros((1, 1, 4, 4))
    cache.append(0, k, k)  # uses both pages
    cache.alloc_seq(1)
    with pytest.raises(MemoryError):
        cache.append(1, k[:, :, :1], k[:, :, :1])


def test_prefix_cache_longest_match():
    pc = PrefixCache()
    pc.insert([1, 2, 3, 4], slot=0)
    pc.insert([1, 2, 9], slot=1)
    assert pc.longest_prefix([1, 2, 3, 4, 5]) == (4, 0)
    assert pc.longest_prefix([1, 2, 9, 9]) == (3, 1)
    assert pc.longest_prefix([7]) == (0, None)
    assert pc.longest_prefix([1, 2, 5]) == (2, 0)  # diverges mid-chain
    pc.invalidate_slot(0)
    # slot 0 no longer serves; slot 1 still holds the shared [1, 2]
    assert pc.longest_prefix([1, 2, 3, 4, 5]) == (2, 1)


# ---------------------------------------------------------------------------
# serving engine (real model)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["qwen2.5-3b", "olmoe-1b-7b", "rwkv6-7b"])
def test_engine_batched_equals_solo(arch):
    cfg = reduced_config(get_config(arch))
    bundle = build_model(cfg)
    params = bundle.init(jax.random.key(0))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, size=10).astype(np.int32)
               for _ in range(3)]

    eng = ServingEngine(bundle, params, slots=3, max_len=32)
    for i, p in enumerate(prompts):
        eng.submit(ServeRequest(i, p, max_new_tokens=5))
    batched = {r.req_id: r.generated for r in eng.run_to_completion()}

    for i, p in enumerate(prompts):
        solo_eng = ServingEngine(bundle, params, slots=1, max_len=32)
        solo_eng.submit(ServeRequest(i, p, max_new_tokens=5))
        solo = solo_eng.run_to_completion()[0]
        assert solo.generated == batched[i], f"{arch}: req {i} diverged"

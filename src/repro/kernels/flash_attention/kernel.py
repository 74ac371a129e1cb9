"""Blocked causal (sliding-window) flash attention — Pallas TPU kernel.

Prefill hot-spot for every attention arch.  TPU-native tiling:
  * grid (B, H, n_q, n_kv); the kv dim is ARBITRARY (sequential) so the
    online-softmax accumulators live in VMEM scratch across kv steps;
  * q/k/v blocks are (block_q, head_dim) / (block_kv, head_dim) VMEM tiles,
    MXU-aligned (block sizes multiples of 128 on the contraction layout);
  * GQA without materialising repeats: the k/v index_map folds the query
    head onto its kv head (h // group);
  * causal (and sliding-window) *block skipping*: fully-masked kv blocks
    are predicated out with pl.when, matching the causal-optimal FLOPs the
    jnp oracle (and the dry-run roofline) count.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                  *, block_q: int, block_kv: int, seq_len: int,
                  window: int, scale: float):
    iq = pl.program_id(2)
    ikv = pl.program_id(3)
    n_kv = pl.num_programs(3)

    q_lo = iq * block_q
    kv_lo = ikv * block_kv

    # --- block-level skip predicates (causal + window band) ---
    below_diag = kv_lo <= q_lo + block_q - 1  # some kv not in the future
    if window > 0:
        in_window = kv_lo + block_kv - 1 > q_lo - window
        live = jnp.logical_and(below_diag, in_window)
    else:
        live = below_diag

    @pl.when(ikv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(live)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (bkv, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
        k_pos = kv_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
        mask = k_pos <= q_pos
        if window > 0:
            mask = jnp.logical_and(mask, k_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        correction = jnp.exp(m_prev - m_new)
        l_new = correction * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = (acc_scr[...] * correction
                        + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                              preferred_element_type=jnp.float32))
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ikv == n_kv - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("window", "block_q", "block_kv", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    window: int = 0, block_q: int = 128, block_kv: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q: (B, H, S, D); k/v: (B, KV, S, D). Causal. Returns (B, H, S, D)."""
    B, H, S, D = q.shape
    KV = k.shape[1]
    G = H // KV
    block_q = min(block_q, S)
    block_kv = min(block_kv, S)
    n_q = pl.cdiv(S, block_q)
    n_kv = pl.cdiv(S, block_kv)
    scale = 1.0 / math.sqrt(D)

    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_kv=block_kv, seq_len=S,
        window=window, scale=scale)

    return pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ikv: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_kv, D), lambda b, h, iq, ikv: (b, h // G, ikv, 0)),
            pl.BlockSpec((1, 1, block_kv, D), lambda b, h, iq, ikv: (b, h // G, ikv, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ikv: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=f"flash_attention_bq{block_q}_bkv{block_kv}",
    )(q, k, v)

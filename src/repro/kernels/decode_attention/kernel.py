"""GQA decode attention over a KV cache — Pallas TPU kernel.

The decode hot-spot for serving: one query token per sequence attends over
a long cache.  This op is *memory-bound* (arithmetic intensity ~ 2·G for
group size G), so the kernel's job is streaming the KV cache HBM->VMEM at
line rate while the grouped queries ride along in registers:

  * grid (B, KV, n_s): the cache seq dim is the ARBITRARY inner dim; the
    flash accumulators (m, l, acc per (group, head_dim)) sit in VMEM
    scratch across cache blocks;
  * the q block is (G, D) for one (batch, kv_head) pair — all grouped
    query heads share the same streamed K/V block (GQA reuse is the whole
    perf story for kv=2 archs like glm4);
  * ``cache_len`` arrives via scalar prefetch (SMEM) and masks the tail
    block; fully-invalid blocks are predicated away with pl.when.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                   *, block_s: int, scale: float):
    isb = pl.program_id(2)
    n_s = pl.num_programs(2)
    cache_len = len_ref[0]
    s_lo = isb * block_s

    @pl.when(isb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(s_lo < cache_len)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)  # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (bs, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        k_pos = s_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos < cache_len, s, NEG_INF)

        m_prev, l_prev = m_scr[...], l_scr[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = corr * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = (acc_scr[...] * corr
                        + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                              preferred_element_type=jnp.float32))
        m_scr[...] = m_new

    @pl.when(isb == n_s - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cache_len: jax.Array, *, block_s: int = 512,
                     interpret: bool = False) -> jax.Array:
    """q: (B, KV, G, D); k_cache/v_cache: (B, KV, Smax, D); cache_len: ().

    Returns (B, KV, G, D).
    """
    B, KV, G, D = q.shape
    Smax = k_cache.shape[2]
    block_s = min(block_s, Smax)
    n_s = pl.cdiv(Smax, block_s)
    scale = 1.0 / math.sqrt(D)

    kernel = functools.partial(_decode_kernel, block_s=block_s, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, KV, n_s),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, isb, lens: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_s, D), lambda b, h, isb, lens: (b, h, isb, 0)),
            pl.BlockSpec((1, 1, block_s, D), lambda b, h, isb, lens: (b, h, isb, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, isb, lens: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=f"decode_attention_bs{block_s}",
    )(jnp.asarray(cache_len, jnp.int32).reshape(1), q, k_cache, v_cache)

"""Chunked WKV6 linear recurrence — Pallas TPU kernel (rwkv6-7b hot-spot).

Per (batch, head): S_t = diag(w_t) S_{t-1} + k_t^T v_t,
                   o_t = r_t S_{t-1} + (r_t . (u (.) k_t)) v_t.

TPU-native chunking (mirrors the jnp oracle in repro.models.rwkv):
  * grid (B*H, n_chunks); chunks are the ARBITRARY inner dim so the
    (D, D) f32 state lives in VMEM scratch across chunk steps;
  * within a chunk, pairwise decays are masked exponentials with all
    exponents <= 0 — numerically stable without the overflow-prone
    1/decay factorisation used by CUDA implementations (hardware
    adaptation note: GPU kernels serialise t inside a warp; on TPU we
    trade that for (C, C) MXU matmuls);
  * per-head bonus ``u`` is indexed via ``bh % H`` in the index_map; it
    is passed as (H, 1, D) so that its (1, D) block spans the last two
    dims in full, which the TPU's block tiling rule requires.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv6_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref,
                 o_ref, sout_ref, s_scr, *, chunk: int):
    ic = pl.program_id(1)
    n_c = pl.num_programs(1)

    @pl.when(ic == 0)
    def _init():
        s_scr[...] = s0_ref[0].astype(jnp.float32)

    r = r_ref[0].astype(jnp.float32)  # (C, D)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)  # (C, D), <= 0
    u = u_ref[0].astype(jnp.float32)  # (1, D) -> broadcast

    # inclusive prefix sum over time as a lower-triangular matmul: the TPU
    # kernel compiler has no cumsum
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tril = (t_idx >= s_idx).astype(jnp.float32)
    cum = jax.lax.dot_general(tril, lw, (((1,), (0,)), ((), ())),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)
    excl = cum - lw  # exclusive

    s0 = s_scr[...]
    # inter-chunk
    r_dec = r * jnp.exp(excl)
    out_inter = jax.lax.dot_general(r_dec, s0, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    # intra-chunk pairwise (t attends tau < t)
    diff = excl[:, None, :] - cum[None, :, :]  # (Ct, Ctau, D)
    t3 = jax.lax.broadcasted_iota(jnp.int32, diff.shape, 0)
    s3 = jax.lax.broadcasted_iota(jnp.int32, diff.shape, 1)
    mask = t3 > s3
    decay = jnp.exp(jnp.where(mask, diff, -jnp.inf))
    A = jnp.sum(r[:, None, :] * k[None, :, :] * decay, axis=-1)  # (Ct, Ctau)
    a_diag = jnp.sum(r * u * k, axis=1, keepdims=True)  # (C, 1)
    out_intra = jax.lax.dot_general(A, v, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    out_intra = out_intra + a_diag * v
    o_ref[0] = (out_inter + out_intra).astype(o_ref.dtype)

    # state to chunk end; the per-row decay of S is applied as diag(w) @ S
    # (a (D, 1) column of w would need a lane-to-sublane relayout)
    total = jnp.sum(lw, axis=0, keepdims=True)  # (1, D)
    k_dec = k * jnp.exp(total - cum)
    d_idx = jax.lax.broadcasted_iota(jnp.int32, (s0.shape[0], s0.shape[0]), 0)
    e_idx = jax.lax.broadcasted_iota(jnp.int32, (s0.shape[0], s0.shape[0]), 1)
    w_diag = jnp.where(d_idx == e_idx, jnp.exp(total), 0.0)
    s_scr[...] = (jax.lax.dot_general(w_diag, s0, (((1,), (0,)), ((), ())),
                                      precision=jax.lax.Precision.HIGHEST,
                                      preferred_element_type=jnp.float32)
                  + jax.lax.dot_general(k_dec, v, (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32))

    @pl.when(ic == n_c - 1)
    def _final():
        sout_ref[0] = s_scr[...]


@functools.partial(jax.jit, static_argnames=("num_heads", "chunk", "interpret"))
def wkv6_scan(r: jax.Array, k: jax.Array, v: jax.Array, logw: jax.Array,
              u: jax.Array, state: jax.Array, *, num_heads: int,
              chunk: int = 64, interpret: bool = False):
    """r/k/v/logw: (BH, S, D); u: (H, D); state: (BH, D, D) f32.

    Returns (out (BH, S, D), final_state (BH, D, D) f32).
    """
    BH, S, D = r.shape
    H = num_heads
    chunk = min(chunk, S)
    n_c = pl.cdiv(S, chunk)

    kernel = functools.partial(_wkv6_kernel, chunk=chunk)
    out, sout = pl.pallas_call(
        kernel,
        grid=(BH, n_c),
        in_specs=[
            pl.BlockSpec((1, chunk, D), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, chunk, D), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, chunk, D), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, chunk, D), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, 1, D), lambda bh, ic: (bh % H, 0, 0)),
            pl.BlockSpec((1, D, D), lambda bh, ic: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, D), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, D, D), lambda bh, ic: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), r.dtype),
            jax.ShapeDtypeStruct((BH, D, D), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((D, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=f"wkv6_scan_c{chunk}",
    )(r, k, v, logw, u.reshape(H, 1, D), state)
    return out, sout

"""Core model building blocks (pure JAX).

Conventions
-----------
* Params are pytrees of arrays; every leaf is declared via :class:`ParamDef`
  which carries shape, init and its TP :class:`PartitionSpec` — a single
  source of truth for ``init``, ``jax.eval_shape`` and pjit shardings.
* Layer-stacked params carry a leading ``L`` dim and are consumed by
  ``jax.lax.scan`` so HLO size is O(1) in depth.
* Attention is implemented as *chunked causal flash* in pure jnp: a static
  unrolled loop over query chunks, each attending to its (static) KV prefix
  slice.  This keeps memory O(S·chunk), achieves causal-optimal FLOPs, and
  lowers on any XLA backend — the Pallas kernels in ``repro.kernels`` are
  the TPU-native implementations of the same contractions and are validated
  against these functions.
* Matmuls accumulate in f32 (``preferred_element_type``); params default
  bf16.
"""
from __future__ import annotations

import math
import os
from dataclasses import dataclass
from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

DEFAULT_DTYPE = jnp.bfloat16
ACC_DTYPE = jnp.float32


def einsum_acc(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    """einsum of the operands as given (bf16 on the MXU), f32 accumulation
    and f32 output."""
    return jnp.einsum(spec, a, b, preferred_element_type=ACC_DTYPE)


# Mesh axis names used across the framework (see repro/launch/mesh.py).
AXIS_POD = "pod"
AXIS_DATA = "data"
AXIS_MODEL = "model"
# Batch dims shard over (pod, data) jointly.
BATCH_AXES = (AXIS_POD, AXIS_DATA)


def shard_hint(x: jax.Array, *entries) -> jax.Array:
    """with_sharding_constraint against whatever mesh axes exist.

    Entries may name axes ('model', ('pod','data')) or be None; axes absent
    from the ambient mesh are dropped, and with no mesh this is a no-op —
    so model code can carry sharding hints without breaking CPU tests.
    """
    names = set(jax.sharding.get_abstract_mesh().axis_names)
    if not names:
        return x

    def fix(e):
        if e is None:
            return None
        if isinstance(e, (tuple, list)):
            kept = tuple(a for a in e if a in names)
            return (kept if len(kept) > 1 else (kept[0] if kept else None))
        return e if e in names else None

    spec = P(*(fix(e) for e in entries))
    return jax.lax.with_sharding_constraint(x, spec)


# ---------------------------------------------------------------------------
# Parameter definitions
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ParamDef:
    """Declarative parameter: shape + init + partition spec."""

    shape: Tuple[int, ...]
    spec: P
    init: str = "normal"  # normal | zeros | ones | decay_init
    scale: Optional[float] = None
    dtype: Any = DEFAULT_DTYPE

    def instantiate(self, key: jax.Array) -> jax.Array:
        if self.init == "zeros":
            return jnp.zeros(self.shape, self.dtype)
        if self.init == "ones":
            return jnp.ones(self.shape, self.dtype)
        if self.init == "decay_init":
            # log-spaced decay init for SSM/RWKV A/w params, in (-8, -4]
            n = self.shape[-1]
            base = -5.0 + 4.0 * (jnp.arange(n, dtype=jnp.float32) / max(n - 1, 1))
            return jnp.broadcast_to(base, self.shape).astype(self.dtype)
        fan_in = self.shape[-2] if len(self.shape) >= 2 else self.shape[-1]
        scale = self.scale if self.scale is not None else 1.0 / math.sqrt(fan_in)
        return (jax.random.normal(key, self.shape, jnp.float32) * scale).astype(self.dtype)


def init_params(defs, rng: jax.Array):
    """Instantiate a pytree of ParamDef with per-leaf folded keys."""
    leaves, treedef = jax.tree.flatten(defs, is_leaf=lambda x: isinstance(x, ParamDef))
    keys = jax.random.split(rng, len(leaves))
    vals = [d.instantiate(k) for d, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


def param_specs(defs):
    return jax.tree.map(lambda d: d.spec, defs,
                        is_leaf=lambda x: isinstance(x, ParamDef))


def param_shapes(defs):
    return jax.tree.map(lambda d: jax.ShapeDtypeStruct(d.shape, d.dtype), defs,
                        is_leaf=lambda x: isinstance(x, ParamDef))


def stack_layer_defs(d: ParamDef, n_layers: int) -> ParamDef:
    """Prepend a layer dim to a ParamDef (for scan-stacked params)."""
    return ParamDef((n_layers,) + d.shape, P(*((None,) + tuple(d.spec))),
                    d.init, d.scale, d.dtype)


def stacked(defs, n_layers: int):
    return jax.tree.map(lambda d: stack_layer_defs(d, n_layers), defs,
                        is_leaf=lambda x: isinstance(x, ParamDef))


# ---------------------------------------------------------------------------
# Norms / activations / embeddings
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(ACC_DTYPE)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps)
    return (normed * (1.0 + scale.astype(ACC_DTYPE))).astype(x.dtype)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    xf = x.astype(ACC_DTYPE)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * scale.astype(ACC_DTYPE)
            + bias.astype(ACC_DTYPE)).astype(x.dtype)


def activate(x: jax.Array, kind: str) -> jax.Array:
    if kind in ("swiglu", "silu"):
        return jax.nn.silu(x)
    if kind in ("geglu", "gelu"):
        return jax.nn.gelu(x)
    if kind == "relu_sq":
        return jnp.square(jax.nn.relu(x))
    raise ValueError(f"unknown activation {kind!r}")


# §Perf knob: when a TP-contracted matmul's partial sums cross chips,
# reducing them in bf16 halves the dominant train-cell collective bytes
# (per-chip accumulation inside the MXU stays f32 either way).  XLA
# places the all-reduce at the dot's output dtype, so emitting bf16 dots
# for row-parallel matmuls moves the reduction to bf16.
BF16_ALLREDUCE = os.environ.get("REPRO_BF16_AR", "0") == "1"


def matmul(x: jax.Array, w: jax.Array) -> jax.Array:
    """x @ w with f32 accumulation, output in x.dtype."""
    if BF16_ALLREDUCE and x.dtype == jnp.bfloat16:
        return jax.lax.dot_general(
            x, w, (((x.ndim - 1,), (0,)), ((), ())))
    return jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=ACC_DTYPE).astype(x.dtype)


def embed_lookup(table: jax.Array, tokens: jax.Array) -> jax.Array:
    """Vocab-sharded embedding lookup (take; SPMD inserts collectives)."""
    return jnp.take(table, tokens, axis=0)


def lm_head_logits(x: jax.Array, table: jax.Array,
                   valid_vocab: Optional[int] = None) -> jax.Array:
    """Project to vocab; table is (V_padded, d) embedding (possibly tied).

    Output logits are pinned vocab-sharded over `model` (and batch over
    (pod, data)) — without the hint SPMD sometimes materializes the full
    vocab per device, which is a ~50 GiB/device blowup at V=256k.
    Padded vocab rows (table rows >= valid_vocab) are masked to -1e30.
    """
    logits = jax.lax.dot_general(
        x, table, (((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=ACC_DTYPE)
    if valid_vocab is not None and valid_vocab < table.shape[0]:
        iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                        logits.ndim - 1)
        logits = jnp.where(iota < valid_vocab, logits, -1e30)
    hint = [BATCH_AXES] + [None] * (logits.ndim - 2) + [AXIS_MODEL]
    return shard_hint(logits, *hint)


def cross_entropy_from_logits(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean CE over (B, S) without materializing one-hot.

    ``logits`` may be vocab-sharded; the reductions over vocab induce
    all-reduces under SPMD.
    """
    logits = logits.astype(ACC_DTYPE)
    lse = jax.nn.logsumexp(logits, axis=-1)
    vocab = logits.shape[-1]
    iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    picked = jnp.sum(jnp.where(iota == labels[..., None], logits, 0.0), axis=-1)
    return jnp.mean(lse - picked)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, n_heads, head_dim); positions: (..., S) or (S,)."""
    head_dim = x.shape[-1]
    half = head_dim // 2
    freq = theta ** (-jnp.arange(0, half, dtype=ACC_DTYPE) / half)
    angles = positions.astype(ACC_DTYPE)[..., None] * freq  # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]  # (..., S, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half].astype(ACC_DTYPE), x[..., half:].astype(ACC_DTYPE)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention — chunked causal flash (pure jnp, causal-FLOP-honest)
# ---------------------------------------------------------------------------


def _attn_one_chunk(q: jax.Array, k: jax.Array, v: jax.Array,
                    q_pos: jax.Array, k_pos: jax.Array,
                    window: int, scale: float,
                    logit_softcap: float = 0.0) -> jax.Array:
    """Full softmax attention of a query chunk over a KV slice.

    q: (B, KV, G, Q, D); k/v: (B, KV, S, D). Returns (B, KV, G, Q, D).
    """
    scores = einsum_acc("bkgqd,bksd->bkgqs", q, k) * scale
    if logit_softcap > 0.0:
        scores = logit_softcap * jnp.tanh(scores / logit_softcap)
    mask = k_pos[None, :] <= q_pos[:, None]  # causal
    if window > 0:
        mask &= k_pos[None, :] > (q_pos[:, None] - window)
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return einsum_acc("bkgqs,bksd->bkgqd", probs, v).astype(q.dtype)


# §Perf knob: query-chunk size of the jnp flash path (smaller = less
# f32 score transient per chunk, more HLO). The Pallas kernel supersedes
# this on real TPU.
Q_CHUNK = int(os.environ.get("REPRO_Q_CHUNK", "1024"))


def causal_flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                           *, window: int = 0, q_chunk: int = 0,
                           logit_softcap: float = 0.0) -> jax.Array:
    """Causal (optionally sliding-window) attention, GQA-aware.

    q: (B, S, H, D);  k, v: (B, S, KV, D).  Returns (B, S, H, D).

    Statically unrolls over query chunks; chunk *i* attends only to its KV
    prefix (or window band), so compiled FLOPs match the causal optimum
    instead of paying the full dense S^2.
    """
    B, S, H, D = q.shape
    if q_chunk <= 0:
        q_chunk = Q_CHUNK
    KV = k.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, S, KV, G, D).transpose(0, 2, 3, 1, 4)  # (B,KV,G,S,D)
    kt = k.transpose(0, 2, 1, 3)  # (B,KV,S,D)
    vt = v.transpose(0, 2, 1, 3)
    q_chunk = min(q_chunk, S)
    n_chunks = (S + q_chunk - 1) // q_chunk
    outs = []
    for i in range(n_chunks):
        lo, hi = i * q_chunk, min((i + 1) * q_chunk, S)
        if window > 0:
            k_lo = max(0, lo - (window - 1))
        else:
            k_lo = 0
        q_i = qg[:, :, :, lo:hi]
        k_i = kt[:, :, k_lo:hi]
        v_i = vt[:, :, k_lo:hi]
        q_pos = jnp.arange(lo, hi)
        k_pos = jnp.arange(k_lo, hi)
        outs.append(_attn_one_chunk(q_i, k_i, v_i, q_pos, k_pos, window,
                                    scale, logit_softcap))
    out = jnp.concatenate(outs, axis=3) if len(outs) > 1 else outs[0]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, D)


def bidirectional_attention(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Full bidirectional attention (encoder / cross-attention).

    q: (B, Sq, H, D); k, v: (B, Sk, KV, D).
    """
    B, Sq, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, Sq, KV, G, D)
    scores = einsum_acc("bqkgd,bskd->bkgqs", qg, k) * scale
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = einsum_acc("bkgqs,bskd->bqkgd", probs, v).astype(q.dtype)
    return out.reshape(B, Sq, H, D)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cache_len: jax.Array, *, window: int = 0,
                     pos: Optional[jax.Array] = None,
                     logit_softcap: float = 0.0) -> jax.Array:
    """Single-token decode attention over a (possibly seq-sharded) cache.

    q: (B, H, D); k_cache/v_cache: (B, KV, Smax, D); cache_len: () or (B,)
    number of valid entries.  Softmax over the cache axis; when the cache
    is sharded over `model` on Smax, SPMD inserts the flash-decoding style
    all-reduce merges automatically.
    """
    B, H, D = q.shape
    KV, Smax = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, KV, G, D)
    scores = einsum_acc("bkgd,bksd->bkgs", qg, k_cache) * scale
    if logit_softcap > 0.0:
        scores = logit_softcap * jnp.tanh(scores / logit_softcap)
    idx = jnp.arange(Smax)
    valid = idx[None] < jnp.broadcast_to(jnp.asarray(cache_len), (B,))[:, None]
    if window > 0 and pos is not None:
        # ring-buffer semantics handled by caller; here mask positions
        valid &= idx[None] > (jnp.broadcast_to(jnp.asarray(pos), (B,))[:, None] - window)
    scores = jnp.where(valid[:, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = einsum_acc("bkgs,bksd->bkgd", probs, v_cache).astype(q.dtype)
    return out.reshape(B, H, D)


# ---------------------------------------------------------------------------
# Attention block (projections + rope + flash / decode)
# ---------------------------------------------------------------------------


def attention_defs(cfg) -> dict:
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    defs = {
        "wq": ParamDef((d, qd), P(None, AXIS_MODEL)),
        "wk": ParamDef((d, kvd), P(None, AXIS_MODEL)),
        "wv": ParamDef((d, kvd), P(None, AXIS_MODEL)),
        "wo": ParamDef((qd, d), P(AXIS_MODEL, None)),
    }
    if cfg.qkv_bias:
        defs["bq"] = ParamDef((qd,), P(AXIS_MODEL), init="zeros")
        defs["bk"] = ParamDef((kvd,), P(AXIS_MODEL), init="zeros")
        defs["bv"] = ParamDef((kvd,), P(AXIS_MODEL), init="zeros")
    return defs


def attention_qkv(p: dict, x: jax.Array, positions: jax.Array, cfg) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B, S, d) -> q (B,S,H,D), k/v (B,S,KV,D), rope applied."""
    B, S, _ = x.shape
    q = matmul(x, p["wq"])
    k = matmul(x, p["wk"])
    v = matmul(x, p["wv"])
    if "bq" in p:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = q.reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attention_block_prefill(p: dict, x: jax.Array, cfg, *, window: int = 0
                            ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Returns (output (B,S,d), (k,v) for the cache, layout (B,KV,S,D))."""
    B, S, _ = x.shape
    positions = jnp.arange(S)
    q, k, v = attention_qkv(p, x, positions, cfg)
    out = causal_flash_attention(q, k, v, window=window)
    out = matmul(out.reshape(B, S, cfg.q_dim), p["wo"])
    kc = k.transpose(0, 2, 1, 3)
    vc = v.transpose(0, 2, 1, 3)
    return out, (kc, vc)


def write_kv(cache: jax.Array, new: jax.Array, idx: jax.Array) -> jax.Array:
    """Write ``new`` (B, KV, 1, D) into ``cache`` (B, KV, Smax, D) at ``idx``.

    ``idx`` is a scalar (uniform position — dry-run / lockstep decode) or a
    per-sequence (B,) vector (continuous batching).
    """
    new = new.astype(cache.dtype)
    idx = jnp.asarray(idx)
    if idx.ndim == 0:
        return jax.lax.dynamic_update_slice_in_dim(cache, new, idx, axis=2)
    return jax.vmap(
        lambda c, n, i: jax.lax.dynamic_update_slice_in_dim(c, n, i, axis=1)
    )(cache, new, idx)


def attention_block_decode(p: dict, x: jax.Array, kv_cache: Tuple[jax.Array, jax.Array],
                           pos: jax.Array, cfg, *, window: int = 0
                           ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """x: (B, d) single token at position ``pos`` (scalar or (B,) int32).

    Writes K/V at ``pos`` (mod Smax for sliding-window ring buffers) and
    attends over the valid cache prefix.
    """
    B, _ = x.shape
    k_cache, v_cache = kv_cache
    Smax = k_cache.shape[2]
    positions = jnp.broadcast_to(jnp.asarray(pos).reshape(-1, 1) if jnp.asarray(pos).ndim
                                 else pos, (B, 1))
    q, k, v = attention_qkv(p, x[:, None, :], positions, cfg)
    write_idx = pos % Smax if window > 0 else pos
    kc = write_kv(k_cache, k.transpose(0, 2, 1, 3), write_idx)
    vc = write_kv(v_cache, v.transpose(0, 2, 1, 3), write_idx)
    cache_len = jnp.minimum(jnp.asarray(pos) + 1, Smax)
    out = decode_attention(q[:, 0], kc, vc, cache_len,
                           window=0)  # ring buffer: all Smax entries valid once full
    out = matmul(out.reshape(B, cfg.q_dim), p["wo"])
    return out, (kc, vc)


# ---------------------------------------------------------------------------
# Dense MLP block
# ---------------------------------------------------------------------------


def mlp_defs(cfg) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    if cfg.activation in ("swiglu", "geglu"):
        return {
            "w_gate": ParamDef((d, f), P(None, AXIS_MODEL)),
            "w_up": ParamDef((d, f), P(None, AXIS_MODEL)),
            "w_down": ParamDef((f, d), P(AXIS_MODEL, None)),
        }
    return {
        "w_up": ParamDef((d, f), P(None, AXIS_MODEL)),
        "w_down": ParamDef((f, d), P(AXIS_MODEL, None)),
    }


def mlp_block(p: dict, x: jax.Array, activation: str) -> jax.Array:
    if "w_gate" in p:
        h = activate(matmul(x, p["w_gate"]), activation) * matmul(x, p["w_up"])
    else:
        h = activate(matmul(x, p["w_up"]), activation)
    return matmul(h, p["w_down"])

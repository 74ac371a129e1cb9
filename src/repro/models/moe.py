"""Top-k routed MoE FFN (granite-moe, olmoe).

Dispatch strategy (TPU-native, collective-free):
  * routing, capacity and scatter/gather run *per batch row* — under pjit
    the batch dim is sharded over (pod, data), so dispatch is local to a
    data shard by construction; no distributed sort, no cross-shard
    all-to-all in the baseline.  (EP over the `model` axis is a perf
    iteration, see EXPERIMENTS.md §Perf.)
  * capacity per row C = ceil(S*K/E * capacity_factor); tokens routed past
    capacity are dropped (scattered to a dummy slot), standard
    GShard/Switch semantics.
  * expert weights are TP-sharded on the d_ff dim over `model`; the second
    grouped matmul contracts d_ff so SPMD inserts the row-parallel
    all-reduce, exactly like the dense MLP.
  * grouped matmuls run over an (E, C+1, d) dispatch buffer — compiled
    FLOPs ≈ S·K·cf active-expert compute, honest for the roofline.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models.layers import (ACC_DTYPE, AXIS_MODEL, BATCH_AXES, ParamDef,
                                 activate, einsum_acc, shard_hint)


def moe_defs(cfg: ArchConfig) -> Dict[str, ParamDef]:
    d, f, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    defs = {
        "router": ParamDef((d, E), P(None, None), dtype=jnp.float32),
        "w_up": ParamDef((E, d, f), P(None, None, AXIS_MODEL)),
        "w_down": ParamDef((E, f, d), P(None, AXIS_MODEL, None)),
    }
    if cfg.activation in ("swiglu", "geglu"):
        defs["w_gate"] = ParamDef((E, d, f), P(None, None, AXIS_MODEL))
    return defs


def capacity_per_row(seq: int, cfg: ArchConfig) -> int:
    c = math.ceil(seq * cfg.experts_per_token / cfg.num_experts
                  * cfg.capacity_factor)
    return max(cfg.experts_per_token, min(c, seq))


def moe_block(p: Dict[str, jax.Array], x: jax.Array, cfg: ArchConfig) -> jax.Array:
    """x: (B, S, d) -> (B, S, d); also accepts (B, d) single-token decode."""
    if x.ndim == 2:
        return moe_block(p, x[:, None, :], cfg)[:, 0]
    B, S, d = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    C = capacity_per_row(S, cfg)

    # --- routing (f32) ---
    logits = jnp.einsum("bsd,de->bse", x.astype(ACC_DTYPE),
                        p["router"].astype(ACC_DTYPE))
    gates = jax.nn.softmax(logits, axis=-1)
    top_g, top_e = jax.lax.top_k(gates, K)  # (B, S, K)
    top_g = top_g / jnp.sum(top_g, axis=-1, keepdims=True)

    # --- position-in-expert via exclusive cumsum over flattened (S*K) ---
    onehot = jax.nn.one_hot(top_e, E, dtype=jnp.int32)  # (B,S,K,E)
    oh_flat = onehot.reshape(B, S * K, E)
    cum = jnp.cumsum(oh_flat, axis=1)  # inclusive
    pos = jnp.sum(oh_flat * (cum - 1), axis=-1)  # (B, S*K) position in expert
    e_flat = top_e.reshape(B, S * K)
    g_flat = top_g.reshape(B, S * K)
    keep = pos < C
    dest = jnp.where(keep, pos, C)  # dummy slot C for dropped tokens
    combined = e_flat * (C + 1) + dest  # (B, S*K) flat dispatch index

    # --- scatter tokens into (B, E*(C+1), d) dispatch buffer ---
    # (sharding hints are load-bearing: without them SPMD replicates the
    # scatter output over `data`, and every device runs the full-batch
    # expert GEMMs — a ~data_ways x FLOP/memory blowup, see §Perf)
    x_rep = jnp.broadcast_to(x[:, :, None, :], (B, S, K, d)).reshape(B, S * K, d)
    x_rep = shard_hint(x_rep, BATCH_AXES, None, None)
    buf = jnp.zeros((B, E * (C + 1), d), x.dtype)
    b_idx = jnp.arange(B)[:, None]
    buf = buf.at[b_idx, combined].add(x_rep)
    buf = shard_hint(buf, BATCH_AXES, None, None)
    # expert-major: E is the batch dim of the grouped matmuls, and XLA's CPU
    # backend executes a bf16 x bf16 -> f32 batched dot only with it leading
    buf = buf.reshape(B, E, C + 1, d).transpose(1, 0, 2, 3)

    # --- grouped expert matmuls (d_ff TP-sharded over `model`) ---
    up = einsum_acc("ebcd,edf->ebcf", buf, p["w_up"]).astype(x.dtype)
    if "w_gate" in p:
        gate = einsum_acc("ebcd,edf->ebcf", buf, p["w_gate"]).astype(x.dtype)
        h = activate(gate, cfg.activation) * up
    else:
        h = activate(up, cfg.activation)
    out_buf = einsum_acc("ebcf,efd->ebcd", h, p["w_down"]).astype(x.dtype)
    out_buf = out_buf.transpose(1, 0, 2, 3)

    # --- gather back + weighted combine over K ---
    out_flat = shard_hint(out_buf.reshape(B, E * (C + 1), d),
                          BATCH_AXES, None, None)
    picked = jnp.take_along_axis(out_flat, combined[:, :, None], axis=1)
    picked = shard_hint(picked, BATCH_AXES, None, None)
    picked = picked * (g_flat * keep.astype(g_flat.dtype))[:, :, None].astype(x.dtype)
    return picked.reshape(B, S, K, d).sum(axis=2)


def load_balance_loss(logits: jax.Array, top_e: jax.Array, E: int) -> jax.Array:
    """Switch-style auxiliary load-balancing loss (optional add-on)."""
    probs = jax.nn.softmax(logits.astype(ACC_DTYPE), axis=-1)
    me = jnp.mean(probs.reshape(-1, E), axis=0)
    ce = jnp.mean(
        jax.nn.one_hot(top_e.reshape(-1), E, dtype=ACC_DTYPE), axis=0)
    return E * jnp.sum(me * ce)

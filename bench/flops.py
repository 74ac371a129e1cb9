"""Operations and bytes a step needs, from the configuration file's sizes.

Copied from the arithmetic of ``serving/costmodel.py`` (``flops_per_token``,
``kv_bytes_per_seq``) so that a later program change cannot move the
yardstick.  Counts are what the model needs, not what a program happens
to compute: attention counts the causal half, the output head counts only
the positions whose logits are used.
"""
from __future__ import annotations

BYTES = 2  # bfloat16 weights and KV cache


def _sizes(cfg: dict):
    d = cfg["hidden_size"]
    qd = cfg["num_attention_heads"] * cfg["head_dim"]
    kvd = cfg["num_key_value_heads"] * cfg["head_dim"]
    return d, qd, kvd, cfg["intermediate_size"], cfg["num_hidden_layers"]


def layer_params(cfg: dict) -> int:
    """Parameters one token multiplies by in one layer: attention's four
    projections and the SwiGLU feed-forward's three."""
    d, qd, kvd, f, _ = _sizes(cfg)
    return d * qd + 2 * d * kvd + qd * d + 3 * d * f


def head_flops(cfg: dict) -> float:
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def token_flops(cfg: dict, context: int) -> float:
    """Forward FLOPs of one token that attends to ``context`` positions,
    without the output head."""
    _, qd, _, _, L = _sizes(cfg)
    return L * (2.0 * layer_params(cfg) + 4.0 * qd * context)


def decode_flops(cfg: dict, context: int) -> float:
    """One decoded token (one engine decode step of one slot)."""
    return token_flops(cfg, context) + head_flops(cfg)


def prefill_flops(cfg: dict, length: int) -> float:
    """A full prefill of ``length`` tokens: causal attention over the
    prompt, logits of the last position only."""
    _, qd, _, _, L = _sizes(cfg)
    linear = 2.0 * L * layer_params(cfg) * length
    attn = 4.0 * qd * L * length * (length + 1) / 2.0
    return linear + attn + head_flops(cfg)


def kv_bytes_per_token(cfg: dict) -> int:
    _, _, kvd, _, L = _sizes(cfg)
    return 2 * L * kvd * BYTES


def decode_step_bytes(cfg: dict, kv_tokens: int) -> float:
    """Least bytes a batched decode step reads: every weight once, the
    output head, and the KV cache of the active slots' real lengths
    (``kv_tokens`` positions in all)."""
    weights = (cfg["num_hidden_layers"] * layer_params(cfg)
               + cfg["vocab_size"] * cfg["hidden_size"])
    return BYTES * weights + kv_bytes_per_token(cfg) * kv_tokens

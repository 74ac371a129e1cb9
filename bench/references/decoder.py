"""Plain reference of a decoder-only LM with grouped-query attention and a
dense SwiGLU feed-forward.

Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST``: the whole
sequence at once, no cache, no batching, no kernels.  It imports nothing of the program.  It reads the
weights the benchmark made, in the program's layout: an RMSNorm weight is
stored as ``weight - 1``, matrices are ``(in, out)`` and stacked by layer,
the embedding is tied to the
output head and has padding rows past ``vocab_size``.

The control (``control=``) is the same reference computed in a precision
below the configuration's bfloat16: every linear layer's operands rounded
to ``fp8`` (float8_e4m3fn, weights scaled per output channel, activations
per token, the embedding and output head per row) or to ``int8`` with the
same scales; attention and every sum stay float32.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
ein = partial(jnp.einsum, precision=HIGHEST)


def f32(w):
    return w.astype(jnp.float32)


def rounded(x, axis, mode):
    """``x`` (float32) rounded as ``mode`` stores it, one scale per slice
    along ``axis``; unchanged for mode None."""
    if mode is None:
        return x
    top = 127.0 if mode == "int8" else 448.0
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    scale = jnp.where(scale == 0, 1.0, scale)
    if mode == "int8":
        q = jnp.clip(jnp.round(x / scale), -127, 127)
    else:
        q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def linear(spec, x, w, control):
    """einsum of an activation and a weight whose contraction axis is the
    weight's second to last; under a control both are rounded first."""
    return ein(spec, rounded(x, -1, control), rounded(f32(w), -2, control))


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + f32(w))


def rope(x, theta):
    """x: (T, heads, D), rotate-half form, positions 0..T-1."""
    T, _, D = x.shape
    half = D // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(p, x, cfg, control):
    T = x.shape[0]
    H, KV, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    q = linear("td,dq->tq", x, p["wq"], control)
    k = linear("td,dq->tq", x, p["wk"], control)
    v = linear("td,dq->tq", x, p["wv"], control)
    if "bq" in p:
        q, k, v = q + f32(p["bq"]), k + f32(p["bk"]), v + f32(p["bv"])
    q = rope(q.reshape(T, H, D), cfg["rope_theta"])
    k = rope(k.reshape(T, KV, D), cfg["rope_theta"])
    v = v.reshape(T, KV, D)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    scores = ein("thd,shd->hts", q, k) / jnp.sqrt(jnp.float32(D))
    causal = jnp.tril(jnp.ones((T, T), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    out = ein("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)
    return linear("tq,qd->td", out.reshape(T, H * D), p["wo"], control)


def dense_ffn(p, x, control):
    h = jax.nn.silu(linear("td,df->tf", x, p["w_gate"], control))
    h = h * linear("td,df->tf", x, p["w_up"], control)
    return linear("tf,fd->td", h, p["w_down"], control)


def hidden_states(params, tokens, cfg, control=None):
    """tokens (T,) int32 -> final-normed hidden states (T, d), float32."""
    eps = cfg["rms_norm_eps"]
    x = rounded(f32(params["embed"][tokens]), -1, control)

    def layer(x, p):
        x = x + attention(p["attn"], rms_norm(x, p["ln1"], eps), cfg,
                          control)
        return x + dense_ffn(p["mlp"], rms_norm(x, p["ln2"], eps),
                             control), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return rms_norm(x, params["final_norm"], eps)


def logits_at(params, hidden, positions, cfg, control=None):
    """Logits over the vocabulary at ``positions`` (n,), float32 (n, V)."""
    head = params.get("lm_head", params["embed"])[:cfg["vocab_size"]]
    return linear("nd,dv->nv", hidden[positions],
                  jnp.swapaxes(head, 0, 1), control)

"""Seeded random weights, made on the device in one jitted call.

The benchmark makes the weights, not the program: the program serves them
and the plain reference reads the same arrays, so neither side's numbers
come from the other.  The tree follows the layout the program declares
(``bundle.shapes()``: names, shapes, dtypes); every leaf is checked against
the configuration file's published sizes before anything is drawn.

Values: matrices N(0, 1/fan_in), except the query and key projections at
N(0, 2/fan_in), so that attention scores spread with a standard deviation
of about 2 and each query leans on a few tens of positions, as a trained
model's do (with 1/fan_in every position weighs about the same, and a
wrong cache entry would hardly move a logit); the tied embedding N(0,
0.02^2), so that the output does not just repeat the input token (with a
unit embedding the residual stream carries the current token's own row,
and greedy decoding copies it); RMSNorm scales (stored as ``weight - 1``)
and attention biases N(0, 0.1), drawn and not left at zero so that the
comparison sees a program that skips them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.traffic import seed_words

SMALL = ("ln1", "ln2", "final_norm", "bq", "bk", "bv")


def leaf_name(path) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)


def expected_shapes(cfg: dict, padded_vocab: int) -> dict:
    """Leaf name -> shape, from the configuration file alone."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    qd = cfg["num_attention_heads"] * cfg["head_dim"]
    kvd = cfg["num_key_value_heads"] * cfg["head_dim"]
    f = cfg["intermediate_size"]
    out = {"embed": (padded_vocab, d), "final_norm": (d,),
           "layers/ln1": (L, d), "layers/ln2": (L, d),
           "layers/attn/wq": (L, d, qd), "layers/attn/wk": (L, d, kvd),
           "layers/attn/wv": (L, d, kvd), "layers/attn/wo": (L, qd, d)}
    if cfg.get("attention_bias"):
        out.update({"layers/attn/bq": (L, qd), "layers/attn/bk": (L, kvd),
                    "layers/attn/bv": (L, kvd)})
    out.update({"layers/mlp/w_gate": (L, d, f), "layers/mlp/w_up": (L, d, f),
                "layers/mlp/w_down": (L, f, d)})
    return out


def check_layout(shapes, cfg: dict) -> None:
    """Refuse a program whose parameter tree differs from the published
    sizes (a cut width would otherwise go unseen)."""
    flat = {leaf_name(p): tuple(s.shape)
            for p, s in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    vocab_rows = flat.get("embed", (0,))[0]
    if vocab_rows < cfg["vocab_size"]:
        raise ValueError(f"embedding has {vocab_rows} rows, fewer than the "
                         f"vocabulary's {cfg['vocab_size']}")
    want = expected_shapes(cfg, vocab_rows)
    if flat != want:
        diff = sorted(set(flat.items()) ^ set(want.items()))
        raise ValueError(f"program parameters differ from {cfg['name']}: "
                         f"{diff}")


def make_weights(shapes, seed: int):
    """One jitted call: every leaf of ``shapes`` drawn from ``seed``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [leaf_name(p) for p, _ in flat]
    specs = [(tuple(s.shape), s.dtype) for _, s in flat]

    def draw(key):
        leaves = []
        for i, (name, (shape, dtype)) in enumerate(zip(names, specs)):
            k = jax.random.fold_in(key, i)
            last = name.rsplit("/", 1)[-1]
            if last in SMALL:
                scale = 0.1
            elif name == "embed":
                scale = 0.02
            elif last in ("wq", "wk"):
                scale = np.sqrt(2.0 / shape[-2])
            else:
                scale = 1.0 / np.sqrt(shape[-2])
            leaves.append((jax.random.normal(k, shape, jnp.float32)
                           * scale).astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    key = jax.random.wrap_key_data(jnp.asarray(seed_words(seed)),
                                   impl="threefry2x32")
    return jax.block_until_ready(jax.jit(draw)(key))

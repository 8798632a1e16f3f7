"""Weights made from the seed, on the device, in one jitted call.

The layout is the dense decoder's parameter tree as the program's train
step and the plain reference both read it: `embed` (V, d), `blocks` with
every leaf stacked over the layers, `final_norm`, and `lm_head` (d, V)
when the head is not tied. Matrices are N(0, 0.02); norm weights are the
offset from 1, so 0. Everything is made in the configuration's parameter
type.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

STD = 0.02


def shapes(conf: dict) -> dict:
    """{name: shape} of every leaf, `blocks/<leaf>` stacked over layers."""
    L = conf["num_hidden_layers"]
    d, f, V = conf["hidden_size"], conf["intermediate_size"], conf["vocab_size"]
    H, KV = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = d // H
    out = {"embed": (V, d), "final_norm": (d,)}
    if not conf["tie_word_embeddings"]:
        out["lm_head"] = (d, V)
    out.update({
        "blocks/ln1": (L, d), "blocks/wq": (L, d, H * hd),
        "blocks/wk": (L, d, KV * hd), "blocks/wv": (L, d, KV * hd),
        "blocks/wo": (L, H * hd, d), "blocks/ln2": (L, d),
        "blocks/w_gate": (L, d, f), "blocks/w_up": (L, d, f),
        "blocks/w_down": (L, f, d),
    })
    return out


def count(conf: dict) -> int:
    n = 0
    for shape in shapes(conf).values():
        size = 1
        for s in shape:
            size *= s
        n += size
    return n


def _make(conf: dict, key) -> dict:
    dtype = jnp.dtype(conf["dtypes"]["params"])
    items = sorted(shapes(conf).items())
    keys = jax.random.split(key, len(items))
    params: dict = {}
    for k, (name, shape) in zip(keys, items):
        if name.endswith(("norm", "ln1", "ln2")):
            leaf = jnp.zeros(shape, dtype)
        else:
            leaf = (STD * jax.random.normal(k, shape, jnp.float32)).astype(dtype)
        node = params
        *parents, last = name.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return params


def make(conf: dict, seed: int, sharding, extra=None):
    """The weights for `seed`, placed by `sharding`. `extra(params)`, when
    given, builds more state from them inside the same jitted call, and the
    call returns `extra(params)` instead."""
    def build(key):
        params = _make(conf, key)
        return params if extra is None else extra(params)
    return jax.jit(build, out_shardings=sharding)(jax.random.PRNGKey(seed))

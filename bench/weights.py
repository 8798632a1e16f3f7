"""Weights made from the seed, on the device, in one jitted call.

The layout is the configuration's reference module's (`shapes`, see
`bench/refs/__init__.py`): the parameter tree as the program's train step
and the plain reference both read it, every `blocks/<leaf>` stacked over
the layers. The rule for a leaf: matrices are N(0, 0.02), norm weights are
the offset from 1, so 0, and everything is made in the configuration's
parameter type; the module's optional `leaf_rules` gives the leaves that
differ (a MoE router held in float32).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import refs

STD = 0.02
INITS = ("normal", "zeros")


def shapes(conf: dict) -> dict:
    """{name: shape} of every leaf, `blocks/<leaf>` stacked over layers."""
    return refs.module(conf).shapes(conf)


def rules(conf: dict) -> dict:
    """{name: {"dtype", "init"}} of every leaf: the rule above, with the
    reference module's `leaf_rules` over it."""
    ref = refs.module(conf)
    own = ref.leaf_rules(conf) if hasattr(ref, "leaf_rules") else {}
    names = shapes(conf)
    unknown = sorted(set(own) - set(names))
    if unknown:
        raise ValueError(f"{conf['reference']}.leaf_rules names no leaf of "
                         f"its layout: {', '.join(unknown)}")
    out = {}
    for name in names:
        rule = {"dtype": conf["dtypes"]["params"],
                "init": ("zeros" if name.endswith(("norm", "ln1", "ln2"))
                         else "normal")}
        rule.update(own.get(name, {}))
        if rule["init"] not in INITS:
            raise ValueError(f"{name}: init {rule['init']!r} is none of "
                             f"{INITS}")
        out[name] = rule
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
    leaf_rules = rules(conf)
    items = sorted(shapes(conf).items())
    keys = jax.random.split(key, len(items))
    params: dict = {}
    for k, (name, shape) in zip(keys, items):
        dtype = jnp.dtype(leaf_rules[name]["dtype"])
        if leaf_rules[name]["init"] == "zeros":
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

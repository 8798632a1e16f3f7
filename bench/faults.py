"""Faults planted under the timed path, to show that the check catches them.

Used by `bench/calibrate.py` (readings on the chip) and `bench/tests/`
(the same faults at toy widths on the CPU); never by `bench/run.py`.

* `state_unchanged`: the step returns its state as it came in;
* `half_batch`: the loss, and so the gradient, is the mean over the first
  half of each chip's batch (rows, or tokens where a chip holds one row);
* `no_exchange`: the gradient and loss sync between chips is left out,
  so each chip steps on its own gradient.

Each is a context manager that holds while a `Program` is built and run.
"""
from __future__ import annotations

import contextlib
import dataclasses

import jax
import jax.numpy as jnp


def _half(batch: dict) -> dict:
    rows, seq = batch["tokens"].shape
    if rows >= 2:
        return {k: v[:rows // 2] for k, v in batch.items()}
    return {k: v[:, :seq // 2] for k, v in batch.items()}


class _NoSync:
    """jax.lax with psum left out."""

    def __getattr__(self, name):
        return getattr(jax.lax, name)

    @staticmethod
    def psum(x, axis_name, **kw):
        return x


@contextlib.contextmanager
def _patched(module, **attrs):
    old = {k: getattr(module, k) for k in attrs}
    for k, v in attrs.items():
        setattr(module, k, v)
    try:
        yield
    finally:
        for k, v in old.items():
            setattr(module, k, v)


@contextlib.contextmanager
def planted(name: str):
    """Hold fault `name` for the block; yields the `wrap_model` that a
    `Program` takes (None where the fault needs none)."""
    import repro.train.step as step_mod
    if name == "state_unchanged":
        def frozen(params, grads, opt_state, lr, cfg):
            return params, opt_state, jnp.zeros((), jnp.float32)
        with _patched(step_mod, update=frozen):
            yield None
    elif name == "half_batch":
        def wrap(model):
            return dataclasses.replace(
                model, loss=lambda p, b: model.loss(p, _half(b)))
        yield wrap
    elif name == "no_exchange":
        with _patched(step_mod, lax=_NoSync(),
                      optcc_allreduce_tree=lambda tree, *a, **k: tree):
            yield None
    else:
        raise ValueError(f"unknown fault {name!r}")


"""The one generator of traffic: batches and fault states from a mix file.

A mix (`bench/traffic/<name>.json`) gives the data-parallel width `dp`,
`seq_len`, `rows_per_chip`, and `faults`:

* `{"kind": "healthy"}`: every step on the healthy program (psum);
* `{"kind": "steady", "straggler": s, "ell": l}`: every step on the
  degraded program with member s slowed by l;
* `{"kind": "trace", "steps_per_state": n, "ells": [...]}`: healthy and
  degraded states alternate, n steps each; every degraded state draws its
  straggler from 0..dp-1 and its slowdown from `ells`.

A fault state here is `None` (healthy) or `(straggler, ell)`. Set-up runs
`first_steps` steps on the window's own step (default 3); a trace runs one
on each program instead: healthy, then every straggler in an order drawn
from the seed. The reference follows exactly those steps.

Batches are uniform token ids, drawn with numpy from (seed, step), so
every step's rows differ and the same seed gives the same rows.
"""
from __future__ import annotations

import itertools
from typing import Iterator, Optional

import numpy as np

Fault = Optional[tuple]


def batch(seed: int, step: int, rows: int, seq_len: int, vocab: int) -> dict:
    """{tokens, labels}: (rows, seq_len) int32, labels the next token."""
    rng = np.random.default_rng([seed, step])
    seq = rng.integers(0, vocab, size=(rows, seq_len + 1), dtype=np.int32)
    return {"tokens": seq[:, :-1], "labels": seq[:, 1:]}


class Schedule:
    """The fault states of one run, drawn from the seed."""

    def __init__(self, mix: dict, seed: int):
        self.mix = mix
        self.dp = mix["dp"]
        self.faults = mix["faults"]
        self.kind = self.faults["kind"]
        if self.kind not in ("healthy", "steady", "trace"):
            raise ValueError(f"unknown fault kind {self.kind!r}")
        if self.kind != "healthy" and self.dp < 3:
            raise ValueError("a degraded member needs dp >= 3")
        self.rng = np.random.default_rng([seed, 1])

    def _steady(self) -> Fault:
        if self.kind == "healthy":
            return None
        return (self.faults["straggler"], float(self.faults["ell"]))

    def first(self) -> list:
        """The fault state of each set-up step."""
        if self.kind != "trace":
            return [self._steady()] * int(self.mix.get("first_steps", 3))
        ells = self.faults["ells"]
        order = self.rng.permutation(self.dp)
        return [None] + [(int(s), float(ells[i % len(ells)]))
                         for i, s in enumerate(order)]

    def window(self) -> Iterator[tuple]:
        """(fault state, steps) for the window, forever. Steady kinds give
        one state with no end; a trace gives whole healthy+degraded cycles."""
        if self.kind != "trace":
            yield self._steady(), None
            return
        n = int(self.faults["steps_per_state"])
        ells = self.faults["ells"]
        for _ in itertools.count():
            yield None, n
            yield (int(self.rng.integers(self.dp)),
                   float(ells[int(self.rng.integers(len(ells)))])), n

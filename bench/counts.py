"""What a step must compute and move, from the configuration's shapes alone.

* `flops_per_token`: the model FLOPs of one training token, as the
  configuration's reference module counts them (its docstring states the
  formula: PaLM's 6N + 12 L H Q T for `bench/refs/dense_decoder.py`).
* `allreduce_least_bytes`: the bytes each of p members must send in an
  AllReduce of n elements, 2n(p-1)/p, the bandwidth-optimal amount that
  no algorithm can go under.
* `peaks`: the chip's published peaks from `peaks.json`, by device kind.
"""
from __future__ import annotations

import json
import pathlib

from bench import refs

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def flops_per_token(conf: dict, seq_len: int) -> int:
    """Forward and backward FLOPs of one token at sequence length T."""
    return refs.module(conf).flops_per_token(conf, seq_len)


def allreduce_least_bytes(n: int, p: int, itemsize: int) -> float:
    """Bytes each member sends, at least, to AllReduce n elements."""
    if p < 2:
        return 0.0
    return 2.0 * n * (p - 1) / p * itemsize


def peaks(device_kind: str) -> dict:
    """The peak table's row for `device_kind`; an unknown kind is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]

"""What a step must compute and move, from the configuration's shapes alone.

* `flops_per_token`: the model FLOPs of one training token, by PaLM's
  formula (Chowdhery et al. 2022, appendix B): 6N + 12 L H Q T, where N
  counts every weight that enters a matrix product (the vocabulary head
  included, the embedding lookup not) and the second term is attention's
  two products over the whole sequence T. Recomputed work does not count.
* `allreduce_least_bytes`: the bytes each of p members must send in an
  AllReduce of n elements, 2n(p-1)/p, the bandwidth-optimal amount that
  no algorithm can go under.
* `peaks`: the chip's published peaks from `peaks.json`, by device kind.
"""
from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def matmul_params(conf: dict) -> int:
    """N: weights that enter a matrix product, per token, forward."""
    d, f, V = conf["hidden_size"], conf["intermediate_size"], conf["vocab_size"]
    H, KV = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = d // H
    per_layer = d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * f
    return conf["num_hidden_layers"] * per_layer + d * V


def flops_per_token(conf: dict, seq_len: int) -> int:
    """Forward and backward FLOPs of one token at sequence length T."""
    L, H = conf["num_hidden_layers"], conf["num_attention_heads"]
    Q = conf["hidden_size"] // H
    return 6 * matmul_params(conf) + 12 * L * H * Q * seq_len


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

"""bench/counts.py, and dense_decoder's FLOP count, against XLA's own count
and against the ring's bytes."""
import dataclasses
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from conftest import CONFIGS, config_file, mix_cell
from bench import counts, harness, weights
from bench.refs import dense_decoder

# Elementwise work that PaLM's formula leaves out and XLA counts, as a
# bound per unit: the optimizer's clip and AdamW per parameter, and per
# activation element of a token (norms, rotary, softmax of the scores and
# of the vocabulary, SwiGLU's gate, casts) forward and backward.
PER_PARAM = 60
PER_ACTIVATION = 60
DENSE = [c for c in CONFIGS
         if config_file(c)["reference"] == "dense_decoder"]


def _activations_per_token(conf: dict, seq_len: int) -> int:
    d, f = conf["hidden_size"], conf["intermediate_size"]
    H = conf["num_attention_heads"]
    return (conf["num_hidden_layers"] * (6 * d + 3 * f + 2 * H * seq_len)
            + 2 * conf["vocab_size"])


@pytest.mark.parametrize("config", DENSE)
@pytest.mark.parametrize("seq_len", [128, 256])
def test_flops_per_token_against_cost_analysis(config, seq_len):
    """At toy widths, with the layers and the loss unrolled so that XLA
    counts every iteration, the step's counted FLOPs exceed the formula by
    no more than the elementwise work."""
    from repro.models import build_model
    cell = mix_cell("dp1.step", config)
    cell = dataclasses.replace(
        cell, mix=dict(cell.mix, seq_len=seq_len),
        conf=dict(cell.conf, hidden_size=256, intermediate_size=512,
                  vocab_size=1024))
    prog = harness.Program(cell, jax.devices()[:1])
    prog.cfg = prog.cfg.replace(scan_layers=False, logits_chunk=seq_len)
    prog.model = build_model(prog.cfg)
    step, _ = prog.rebuild(None)
    compiled = step.lower(prog.init_state(0), prog.batch(0, 0)).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    tokens = prog.rows * seq_len
    formula = dense_decoder.flops_per_token(cell.conf, seq_len) * tokens
    slack = (PER_PARAM * weights.count(cell.conf)
             + PER_ACTIVATION * _activations_per_token(cell.conf, seq_len)
             * tokens)
    assert formula <= cost["flops"] <= formula + slack


def test_flops_per_token_by_hand():
    conf = {"num_hidden_layers": 2, "hidden_size": 8, "intermediate_size": 16,
            "num_attention_heads": 2, "num_key_value_heads": 1,
            "vocab_size": 10}
    # per layer: q 8*8, k and v 2*8*4, o 8*8, gate/up/down 3*8*16
    n = 2 * (64 + 64 + 64 + 384) + 8 * 10
    assert dense_decoder.matmul_params(conf) == n
    assert dense_decoder.flops_per_token(conf, 32) == (6 * n
                                                       + 12 * 2 * 2 * 4 * 32)
    # bench/counts.py counts by the module that `reference` names
    assert counts.flops_per_token(dict(conf, reference="dense_decoder"),
                                  32) == 6 * n + 12 * 2 * 2 * 4 * 32


@pytest.mark.parametrize("n", [4096, 12288])
def test_allreduce_least_bytes_is_what_the_ring_sends(n):
    """At p = 4 the ring program sends 2(p-1) chunks of n/p: the least."""
    from repro.comms import ring_allreduce
    p = 4
    mesh = Mesh(np.array(jax.devices()[:p]), ("data",))
    prog = jax.jit(jax.shard_map(lambda x: ring_allreduce(x, "data"),
                                 mesh=mesh, in_specs=P("data"),
                                 out_specs=P("data"), check_vma=False))
    text = prog.lower(jnp.zeros((p * n,), jnp.float32)).compile().as_text()
    sent = sum(int(m.group(1)) * 4 for m in re.finditer(
        r"= f32\[(\d+)\]\{0\} collective-permute\(", text))
    assert sent == counts.allreduce_least_bytes(n, p, 4) == 2 * n * 3 / 4 * 4
    assert counts.allreduce_least_bytes(n, 1, 2) == 0


def test_peaks_unknown_kind_is_an_error():
    assert counts.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        counts.peaks("cpu")

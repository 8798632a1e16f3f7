"""Compile each configuration's timed programs for a described TPU v5e 2x2,
without a chip: the degraded four-chip failover step at the file's depth,
the healthy one, and the reference on one chip. Nothing runs; a pass says
the chip's compiler accepts them and fits them in HBM, and nothing about
results or times.
"""
import json

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from conftest import CONFIGS, ROOT, config_file
from bench import harness, refs, weights
from repro.comms.collectives import CHUNK_ALIGN

HBM = 15.75e9        # what the compiler lets one v5e program use


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _cell(config: str, chips: int):
    """`config` at its file's widths and depth under the four-chip mix."""
    conf = config_file(config)
    mix = json.loads((ROOT / "bench" / "traffic" / "dp4.degraded.json")
                     .read_text())
    return harness.Cell(config, chips, config, conf, "dp4.degraded", mix,
                        {}, [], [])


@pytest.mark.parametrize("degraded", [True, False])
@pytest.mark.parametrize("config", CONFIGS)
def test_four_chip_step_compiles_at_file_depth(topo, config, degraded):
    from repro.optim import init_state
    from repro.train import TrainState
    cell = _cell(config, 4)
    prog = harness.Program(cell, topo.devices)
    rep = NamedSharding(prog.mesh, P())
    params = jax.eval_shape(
        lambda: weights._make(cell.conf, jax.random.PRNGKey(0)))
    state = TrainState(params,
                       jax.eval_shape(lambda p: init_state(p, prog.opt),
                                      params),
                       jax.ShapeDtypeStruct((), jnp.int32))
    state = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=rep), state)
    tok = jax.ShapeDtypeStruct((prog.rows, prog.seq_len), jnp.int32,
                               sharding=prog.to_mesh)
    step, _ = prog.rebuild((1, 2.0) if degraded else None)
    compiled = step.lower(state, {"tokens": tok, "labels": tok}).compile()
    text = compiled.as_text()
    assert ("collective-permute" in text) == degraded
    assert weights.count(cell.conf) == cell.conf["gradient_elements"]
    pad = (-cell.conf["gradient_elements"]) % (3 * CHUNK_ALIGN)
    assert pad == cell.conf["optcc_pad"]


@pytest.mark.parametrize("config", CONFIGS)
def test_reference_fits_one_chip(topo, config):
    cell = _cell(config, 4)
    one = SingleDeviceSharding(topo.devices[0])
    params = jax.eval_shape(
        lambda: weights._make(cell.conf, jax.random.PRNGKey(0)))
    params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one), params)
    rows = cell.mix["dp"] * cell.mix["rows_per_chip"]
    tok = jax.ShapeDtypeStruct((rows, cell.mix["seq_len"]), jnp.int32,
                               sharding=one)
    ref = refs.module(cell.conf)
    m = jax.jit(lambda p, t, y: ref.loss_and_grad(cell.conf, p, t, y)).lower(
        params, tok, tok).compile().memory_analysis()
    total = (m.argument_size_in_bytes + m.temp_size_in_bytes
             + m.output_size_in_bytes)
    assert total < 0.8 * HBM

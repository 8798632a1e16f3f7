"""Compile the chip's main path for a described TPU v5e 2x2, without a chip.

The TPU compiler is installed beside CPU JAX, so it can refuse here what
the chip would refuse: Pallas blocks that break the (8, 128) tiling rule,
kernels that need more VMEM than they may use, programs that do not fit
HBM. Nothing runs; a pass says nothing about results or times.

All of these compiles stay in this one file: only one process at a time
may load the TPU library, and the topology is described in a fixture so
that no other test worker ever loads it.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from chip_smoke import N_LAYERS
from repro.comms.fault import FaultState
from repro.configs import get_config
from repro.kernels.chunk_reduce.kernel import chunk_reduce_pallas
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.wkv.kernel import wkv_pallas
from repro.models import build_model
from repro.optim import AdamWConfig, init_state
from repro.optim.schedules import constant
from repro.train import TrainState, make_dp_failover_step


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("ways,n,dtype,block", [
    (4, 1 << 24, jnp.bfloat16, None),     # a 4-rank gradient, default block
    (16, 1 << 22, jnp.float32, 131072),   # capped: this block overflows VMEM
])
def test_chunk_reduce_compiles(one_chip, ways, n, dtype, block):
    kw = {} if block is None else {"block": block}
    x = jax.ShapeDtypeStruct((ways, n), dtype, sharding=one_chip)
    _compile(lambda a: chunk_reduce_pallas(a, **kw), x)


def test_flash_attention_compiles_at_qwen3_width(one_chip):
    cfg = get_config("qwen3-1.7b")
    S = 4096
    q = jax.ShapeDtypeStruct((1, S, cfg.n_heads, cfg.hd), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, S, cfg.n_kv_heads, cfg.hd), jnp.bfloat16,
                              sharding=one_chip)
    _compile(lambda a, b, c: flash_attention_pallas(a, b, c), q, kv, kv)


def test_wkv_compiles_at_rwkv6_width(one_chip):
    cfg = get_config("rwkv6-7b")
    hd = cfg.ssm_state
    H = cfg.d_model // hd
    x = jax.ShapeDtypeStruct((1, 4096, H, hd), jnp.float32, sharding=one_chip)
    u = jax.ShapeDtypeStruct((H, hd), jnp.float32, sharding=one_chip)
    _compile(lambda r, k, v, w, uu: wkv_pallas(r, k, v, w, uu), x, x, x, x, u)


def test_degraded_failover_step_compiles_on_4_chips(topo):
    """qwen3-1.7b at full width, chip_smoke's depth, one member degraded:
    the step fits HBM and its gradient sync is ppermute (OptCC)."""
    cfg = get_config("qwen3-1.7b").replace(n_layers=N_LAYERS)
    model = build_model(cfg)
    opt = AdamWConfig(weight_decay=0.01)
    mesh = Mesh(np.array(topo.devices), ("data",))
    replicated = NamedSharding(mesh, P())
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    state = TrainState(params, jax.eval_shape(lambda p: init_state(p, opt),
                                              params),
                       jax.ShapeDtypeStruct((), jnp.int32))
    state = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=replicated), state)
    tok = jax.ShapeDtypeStruct((4, 2048), jnp.int32,
                               sharding=NamedSharding(mesh, P("data")))
    step = make_dp_failover_step(model, mesh, opt, constant(1e-3),
                                 FaultState(axis_size=4, straggler=1, ell=2.0))
    compiled = step.lower(state, {"tokens": tok, "labels": tok}).compile()
    assert "collective-permute" in compiled.as_text()
    # The donated state comes back in place: no second copy of it.
    state_bytes = sum(s.size * s.dtype.itemsize
                      for s in jax.tree.leaves(state))
    assert compiled.memory_analysis().alias_size_in_bytes >= state_bytes

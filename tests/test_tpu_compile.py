"""Compile the chip's main path for a described TPU v5e 2x2, without a chip.

The TPU compiler is installed beside CPU JAX, so it can refuse here what
the chip would refuse: Pallas blocks that break the (8, 128) tiling rule,
kernels that need more VMEM than they may use, programs that do not fit
HBM. Nothing runs; a pass says nothing about results or times.

All of these compiles stay in this one file: only one process at a time
may load the TPU library, and the topology is described in a fixture so
that no other test worker ever loads it.
"""
import re

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
from repro.obs import scopes
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


# ----------------------------------------------------------------------------
# the step's named scopes, read from the program the chip compiler makes
# ----------------------------------------------------------------------------

INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
HOP = re.compile(r"hop\d+")
NO_WORK = ("parameter", "constant", "get-tuple-element", "tuple", "bitcast",
           "after-all", "while", "call", "conditional")
# What the step's own op_names may leave outside every scope: its arguments
# (`batch[...]`, `state[...]`), the step counter's `add`, and the constants
# JAX builds at the shard_map's level (hoisted broadcasts, an iota's `le`
# and `convert_element_type`). Instructions without an op_name are the
# compiler's own (copies, layout changes, loop bookkeeping): the
# benchmark's join places those, the program cannot.
UNSCOPED = re.compile(r"(batch|state)\[|jit\(step\)/add$|"
                      r"jit\(step\)/shard_map/(broadcast\.\d+|le|"
                      r"convert_element_type)$")


def _scope(op_name: str):
    """The scope path an op_name names (`model` split into forward and
    backward, `grad_sync` followed down to `hop<t>`), or None."""
    parts = op_name.split("/")[:-1]          # the last names the primitive
    for i, p in enumerate(parts):
        if p == scopes.MODEL:
            return "model/backward" if "transpose(" in op_name \
                else "model/forward"
        if p in scopes.TOP:
            path = [p]
            for q in parts[i + 1:]:
                if q not in scopes.SYNC and not HOP.fullmatch(q):
                    break
                path.append(q)
            return "/".join(path)
    return None


def _toy_step_text(topo, fault: FaultState) -> str:
    cfg = get_config("qwen3-1.7b", smoke=True)
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
    tok = jax.ShapeDtypeStruct((4, 128), jnp.int32,
                               sharding=NamedSharding(mesh, P("data")))
    step = make_dp_failover_step(model, mesh, opt, constant(1e-3), fault)
    return step.lower(state, {"tokens": tok, "labels": tok}).compile().as_text()


@pytest.fixture(scope="module")
def degraded_text(topo):
    return _toy_step_text(topo, FaultState(axis_size=4, straggler=1, ell=2.0))


@pytest.fixture(scope="module")
def healthy_text(topo):
    return _toy_step_text(topo, FaultState(axis_size=4))


def _instructions(text: str, every: bool = False) -> dict:
    """instruction -> (op_name, opcode, shape), for the computations that
    run as operations (fused computations, reducers and loop conditions
    left out unless `every`)."""
    comps: dict = {}
    inner: set = set()
    comp = None
    for line in text.splitlines():
        if line and not line.startswith(" ") and line.rstrip().endswith("{"):
            comp = re.match(r"^(?:ENTRY )?%?([\w.\-]+)", line).group(1)
            continue
        m = INSTRUCTION.match(line)
        if not m:
            continue
        rest = line[m.end():]
        if rest.startswith("("):             # a tuple shape: skip it
            depth = 0
            for i, ch in enumerate(rest):
                depth += ch == "("
                depth -= ch == ")"
                if depth == 0:
                    break
            shape, rest = rest[:i + 1], rest[i + 1:]
        else:
            shape, _, rest = rest.partition(" ")
        opcode = re.match(r"\s*([\w\-]+)\(", rest).group(1)
        if "AllocateBuffer" in line:
            opcode = "after-all"          # reserves memory, does no work
        if opcode != "call":              # a call's body runs as operations
            inner.update(re.findall(
                r"(?:calls|to_apply|condition)=%([\w.\-]+)", line))
        op = OP_NAME.search(line)
        comps.setdefault(comp, {})[m.group(1)] = (op.group(1) if op else "",
                                                  opcode, shape)
    return {n: v for c, instrs in comps.items() if every or c not in inner
            for n, v in instrs.items()}


def test_degraded_step_names_every_stage(degraded_text):
    paths = {_scope(op) for op, *_ in
             _instructions(degraded_text, every=True).values()} - {None}
    want = {"model/forward", "model/backward", scopes.OPTIMIZER}
    want |= {f"{scopes.GRAD_SYNC}/{s}" for s in
             (scopes.FLATTEN, scopes.S3, scopes.S2, scopes.UNFLATTEN,
              scopes.LOSS)}
    want |= {f"{scopes.GRAD_SYNC}/{s}/{scopes.hop(t)}"
             for s in (scopes.S1, scopes.S4) for t in range(3 - 1)}
    assert want <= paths, sorted(want - paths)


def test_every_collective_permute_lies_in_an_optcc_stage(degraded_text):
    instrs = _instructions(degraded_text)
    permutes = [(n, op) for n, (op, code, _) in instrs.items()
                if code.startswith("collective-permute")]
    assert permutes
    stages = {f"{scopes.GRAD_SYNC}/{s}" for s in
              (scopes.S1, scopes.S4, scopes.S3, scopes.S2)}
    for n, op in permutes:
        path = _scope(op) or ""
        assert "/".join(path.split("/")[:2]) in stages, (n, op)


def test_healthy_all_reduce_lies_under_psum(healthy_text):
    instrs = _instructions(healthy_text)
    paths = [_scope(op) for op, code, _ in instrs.values()
             if code.startswith("all-reduce")]
    sync = f"{scopes.GRAD_SYNC}/{scopes.PSUM}"
    assert sync in paths
    assert set(paths) <= {sync, f"{scopes.GRAD_SYNC}/{scopes.LOSS}"}
    assert not any(code.startswith("collective-permute")
                   for _, code, _ in instrs.values())


F32_DIMS = re.compile(r"\bf32\[([\d,]*)\]")


def test_optcc_subring_holds_its_data_flat(degraded_text):
    """S1 and S4 make no f32 array of rank 2 or more, and each of their
    dynamic-update-slices writes a rank-1 buffer: the subring keeps no
    (ph, n/ph) stack, whose row updates the TPU pads and relayouts."""
    stages = {f"{scopes.GRAD_SYNC}/{s}" for s in (scopes.S1, scopes.S4)}
    ops = [(n, code, shape) for n, (op, code, shape)
           in _instructions(degraded_text, every=True).items()
           if "/".join((_scope(op) or "").split("/")[:2]) in stages]
    assert ops
    ranks = {n: [len(d.split(",")) if d else 0
                 for d in F32_DIMS.findall(shape)]
             for n, _, shape in ops}
    stacked = [(n, shape) for n, _, shape in ops
               if max(ranks[n], default=0) > 1]
    assert not stacked, stacked
    updates = [n for n, code, _ in ops if code == "dynamic-update-slice"]
    assert updates
    assert all(ranks[n] == [1] for n in updates), updates


@pytest.mark.parametrize("which", ["degraded", "healthy"])
def test_few_instructions_are_left_without_a_scope(request, which):
    """Every instruction that does work and carries an op_name of the step
    lies in a scope, save the allowance `UNSCOPED` names, which covers
    under 10% of them; read from the program's own op_names, nothing
    inherited."""
    text = request.getfixturevalue(f"{which}_text")
    named = [op for op, code, _ in _instructions(text).values()
             if code not in NO_WORK and op]
    left = [op for op in named if _scope(op) is None]
    assert len(named) > 200
    assert all(UNSCOPED.match(op) for op in left), sorted(
        op for op in left if not UNSCOPED.match(op))
    assert len(left) < 0.1 * len(named), len(left)

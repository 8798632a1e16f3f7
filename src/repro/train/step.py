"""Train-step factories.

Two distribution modes:

* make_gspmd_train_step - the production path: jit + GSPMD over the
  (pod, data, model) mesh. Batch is sharded over pod x data, parameters
  over model (tensor parallel) and optionally data (FSDP); XLA emits the
  gradient reduce-scatters / all-gathers. This is the path the multi-pod
  dry-run lowers and the roofline reads. Optional microbatch gradient
  accumulation (scan) overlaps per-microbatch sync with the next
  microbatch's compute.

* make_dp_failover_step - the fault-tolerant data-parallel path:
  shard_map over a 1-D DP mesh with parameters replicated; gradients are
  produced per-shard and synchronized by an *explicit software collective*
  selected from the live FaultState: XLA psum when healthy,
  comms.optcc_allreduce when a member's link is degraded (the paper's
  algorithm). At production scale each tensor-parallel rank group runs
  exactly this program over its DP peers.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax import named_scope as scope
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.comms import optcc_allreduce_tree
from repro.comms.fault import FaultState
from repro.models.api import Model
from repro.obs import scopes
from repro.optim import AdamWConfig, init_state, update
from repro.train.state import TrainState


# ----------------------------------------------------------------------------
# GSPMD production path
# ----------------------------------------------------------------------------

def batch_spec(mesh: Mesh) -> P:
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    return P(axes if len(axes) > 1 else axes[0])


def param_pspec(path: str, leaf, cfg, mesh: Mesh) -> P:
    """Sharding rule for one parameter leaf.

    TP: last (output-features) dim over 'model' for up-projections,
    first over 'model' for down-projections; embeddings/vocab over
    'model'. FSDP: additionally shard the largest remaining dim over
    'data' when cfg.fsdp (plus 'pod' for very large tensors).
    """
    shape = leaf.shape
    name = path.split("/")[-1]
    ndim = len(shape)
    spec: list = [None] * ndim
    model_dim = None
    if name in ("embed", "lm_head", "pos_embed"):
        # (V, d) / (d, V): shard vocab over model
        model_dim = 0 if name == "embed" else ndim - 1
    elif name in ("wq", "wk", "wv", "w_gate", "w_up", "xq", "xk", "xv",
                  "m_in", "m_xbc", "ck", "cr", "wr", "wk", "wv", "wg",
                  "e_gate", "e_up", "d_gate", "d_up"):
        model_dim = ndim - 1          # output features
    elif name in ("wo", "w_down", "xo", "m_out", "cv", "e_down", "d_down"):
        model_dim = ndim - 2 if ndim >= 2 else None  # input features
    elif name == "router":
        model_dim = None              # small, replicated
    if name in ("e_gate", "e_up", "e_down"):
        n_exp = getattr(cfg, "n_experts", 0) if cfg is not None else 0
        if n_exp >= 64:
            # expert parallelism: experts over model (arctic: 128e).
            spec[1 if ndim == 4 else 0] = "model"
            model_dim = None
        else:
            # TP inside experts: shard the FFN hidden dim over model so
            # the dispatch scatter/gather stays device-local (phi3.5:
            # 16e; EP via GSPMD scatter costs an all-reduce of the full
            # dispatch buffer per layer - measured in SPerf).
            model_dim = ndim - 1 if name in ("e_gate", "e_up") \
                else ndim - 2
    if (model_dim is not None and "model" in mesh.axis_names
            and shape[model_dim] % mesh.shape["model"] == 0):
        spec[model_dim] = "model"
    # FSDP (ZeRO-3 style): shard the largest remaining dim over data.
    # Embedding-like tables are excluded: sharding their feature dim over
    # data forces GSPMD into full rematerialization around the token
    # gather (the vocab dim is already sharded over model).
    if cfg is not None and getattr(cfg, "fsdp", False) \
            and name not in ("embed", "lm_head", "pos_embed"):
        free = [i for i in range(ndim) if spec[i] is None]
        if free:
            i = max(free, key=lambda i: shape[i])
            if shape[i] % mesh.shape["data"] == 0 and shape[i] >= 1024:
                spec[i] = "data"
    return P(*spec)


def shardings_for_params(params, cfg, mesh: Mesh):
    def one(path, leaf):
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        return NamedSharding(mesh, param_pspec(key, leaf, cfg, mesh))
    return jax.tree_util.tree_map_with_path(one, params)


def make_gspmd_train_step(model: Model, mesh: Mesh,
                          opt_cfg: AdamWConfig,
                          lr_fn: Callable,
                          num_microbatches: int = 1,
                          donate: bool = True):
    cfg = model.cfg
    bspec = batch_spec(mesh)

    def loss_fn(params, batch):
        return model.loss(params, batch)

    def step(state: TrainState, batch: dict):
        if num_microbatches > 1:
            from repro.models.shardctx import constrain_batch
            def micro(carry, mb):
                gacc, lacc = carry
                mb = jax.tree.map(
                    lambda a: constrain_batch(a) if a.ndim >= 2 else a, mb)
                l, g = jax.value_and_grad(loss_fn)(state.params, mb)
                return (jax.tree.map(jnp.add, gacc, g), lacc + l), None
            mbs = jax.tree.map(
                lambda x: x.reshape((num_microbatches,
                                     x.shape[0] // num_microbatches)
                                    + x.shape[1:]), batch)
            zero = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            # keep the grad accumulator sharded like the params
            zero = jax.tree.map(jax.lax.with_sharding_constraint, zero,
                                shardings_for_params(state.params, cfg, mesh))
            (grads, loss), _ = lax.scan(micro, (zero, 0.0), mbs)
            grads = jax.tree.map(lambda g: g / num_microbatches, grads)
            loss = loss / num_microbatches
        else:
            loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
        lr = lr_fn(state.step)
        new_params, new_opt, gnorm = update(state.params, grads,
                                            state.opt_state, lr, opt_cfg)
        return (TrainState(new_params, new_opt, state.step + 1),
                {"loss": loss, "grad_norm": gnorm, "lr": lr})

    return step


# ----------------------------------------------------------------------------
# fault-tolerant pure-DP path (shard_map + explicit sync)
# ----------------------------------------------------------------------------

def make_dp_failover_step(model: Model, mesh: Mesh,
                          opt_cfg: AdamWConfig, lr_fn: Callable,
                          fault: FaultState,
                          compression: bool = False):
    """shard_map train step over a 1-D ('data',) mesh.

    Gradient sync: psum when fault.healthy, optcc_allreduce when degraded.
    Re-call this factory (re-jit) whenever `fault` changes - that is the
    NCCL-reinit analogue; the OptCC planner's closed form makes the new
    schedule cheap to produce.

    The step's work runs under the `repro.obs.scopes` names: `model` (loss
    and gradient), `grad_sync` (the sync, the division by the DP width and
    the loss's psum under `loss`) and `optimizer`; the healthy sync is
    `grad_sync/psum`, the degraded one OptCC's stages.

    The returned step donates its `TrainState` argument: rebind it to the
    returned state (`state, m = step(state, batch)`) and do not read the
    old one again. Build the state on `mesh` (`init_train_state(...,
    mesh=mesh)`) so that it is already replicated where the step runs.
    """
    assert mesh.axis_names == ("data",)
    dp = mesh.shape["data"]

    def body(params, opt_state, step_no, batch):
        with scope(scopes.MODEL):
            loss, grads = jax.value_and_grad(model.loss)(params, batch)
        with scope(scopes.GRAD_SYNC):
            if fault.degraded:
                grads = optcc_allreduce_tree(grads, "data",
                                             fault.straggler, dp)
                with scope(scopes.UNFLATTEN):
                    grads = jax.tree.map(lambda g: g / dp, grads)
            else:
                with scope(scopes.PSUM):
                    grads = jax.tree.map(lambda g: lax.psum(g, "data") / dp,
                                         grads)
            with scope(scopes.LOSS):
                loss = lax.psum(loss, "data") / dp
        with scope(scopes.OPTIMIZER):
            lr = lr_fn(step_no)
            new_params, new_opt, gnorm = update(params, grads, opt_state,
                                                lr, opt_cfg)
        return new_params, new_opt, loss, gnorm

    smapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), P(), P("data")),
        out_specs=(P(), P(), P(), P()),
        check_vma=False)

    @functools.partial(jax.jit, donate_argnums=0)
    def step(state: TrainState, batch: dict):
        new_params, new_opt, loss, gnorm = smapped(
            state.params, state.opt_state, state.step, batch)
        return (TrainState(new_params, new_opt, state.step + 1),
                {"loss": loss, "grad_norm": gnorm})

    return step


def init_train_state(model: Model, opt_cfg: AdamWConfig, seed: int = 0,
                     mesh: Optional[Mesh] = None) -> TrainState:
    """Fresh parameters from `seed` and zeroed optimizer state.

    With `mesh`, the state is created replicated over all of its devices
    (what make_dp_failover_step's P() specs expect); without, it lands on
    the default device."""
    def init(key):
        params = model.init(key)
        return TrainState(params, init_state(params, opt_cfg),
                          jnp.zeros((), jnp.int32))
    placement = {} if mesh is None else {
        "out_shardings": NamedSharding(mesh, P())}
    return jax.jit(init, **placement)(jax.random.PRNGKey(seed))

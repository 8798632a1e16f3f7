"""One run of one cell: set-up, the measured window, and the check.

`run.py` is the command; this module holds what it does after it has
found its chips, so that the CPU rehearsals in `bench/tests/` drive the
same functions on virtual devices.

Set-up makes the train state on the device from the seed, builds the
step through the launcher's `rebuild_step`, and runs the mix's first
steps through the window's own call and feed; the reference follows
exactly those steps once the window has closed. The window then drives
what the launcher's loop does: on every fault-state change
`rebuild_step` (planner -> `make_dp_failover_step`), then
`step(state, batch)` on host-made batches put onto the ("data",) mesh,
with at most `LAG` steps in flight and the device drained at the end.
"""
from __future__ import annotations

import collections
import dataclasses
import json
import pathlib
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding
from jax.profiler import TraceAnnotation as span

from bench import compare, refs, weights, workload

ROOT = pathlib.Path(__file__).resolve().parents[1]
LAG = 2            # steps in flight before the host waits for the oldest


# ----------------------------------------------------------------------------
# the cell, from BENCHMARK.json and the files it names
# ----------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: str
    conf: dict          # bench/configs/<config>.json
    traffic: str
    mix: dict           # bench/traffic/<traffic>.json
    limits: dict        # bench/limits/<cell>.json: {number: limit}
    end_to_end: list    # BENCHMARK.json metric entries this cell reports
    per_layer: list


def _json(path: pathlib.Path) -> dict:
    return json.loads(path.read_text())


def _reports(entry: dict, cell: str) -> bool:
    return cell in entry.get("workloads", [cell])


def load_cell(name: str) -> Cell:
    spec = _json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    conf = _json(ROOT / configs[w["config"]]["file"])
    mix = _json(ROOT / "bench" / "traffic" / f"{w['traffic']}.json")
    if mix["dp"] != w["chips"]:
        raise SystemExit(f"{name}: mix {w['traffic']} needs {mix['dp']} "
                         f"chips, the cell asks for {w['chips']}")
    limits = _json(ROOT / "bench" / "limits" / f"{name}.json")
    return Cell(name, w["chips"], w["config"], conf, w["traffic"], mix,
                limits["limits"],
                [m for m in spec["end_to_end"] if _reports(m, name)],
                [m for m in spec["per_layer"] if _reports(m, name)])


def model_config(conf: dict, name: str):
    """The program's ModelConfig for a configuration file: family "dense",
    the dtypes, and the fields that the reference module reads from the
    published keys (`program_settings`), then the file's optional
    `program` object over them, whose keys are ModelConfig fields."""
    from repro.configs.base import ModelConfig
    program = conf.get("program", {})
    known = {f.name for f in dataclasses.fields(ModelConfig)}
    unknown = sorted(set(program) - known)
    if unknown:
        raise ValueError(f"{name}: 'program' sets no ModelConfig field "
                         f"{', '.join(unknown)}")
    return ModelConfig(**{
        "name": name, "family": "dense",
        "param_dtype": conf["dtypes"]["params"],
        "compute_dtype": conf["dtypes"]["compute"],
        "moment_dtype": conf["dtypes"]["adam_moments"],
        **refs.module(conf).program_settings(conf), **program})


# ----------------------------------------------------------------------------
# the system under test
# ----------------------------------------------------------------------------

def host_copy(tree):
    """One replica of every leaf, as numpy arrays in their own dtype."""
    return jax.tree.map(lambda a: np.asarray(a.addressable_shards[0].data),
                        tree)


class Program:
    """The program's train step on a ("data",) mesh of `devices`.

    `wrap_model`, for the fault rehearsals only, replaces the model the
    step is built from."""

    def __init__(self, cell: Cell, devices,
                 wrap_model: Optional[Callable] = None):
        from repro.models import build_model
        from repro.optim import AdamWConfig
        from repro.optim.schedules import constant
        self.cell = cell
        conf = cell.conf
        self.cfg = model_config(conf, cell.config)
        self.model = build_model(self.cfg)
        if wrap_model is not None:
            self.model = wrap_model(self.model)
        o = conf["optimizer"]
        self.opt = AdamWConfig(b1=o["b1"], b2=o["b2"], eps=o["eps"],
                               weight_decay=o["weight_decay"],
                               clip_norm=o["clip_norm"],
                               moment_dtype=conf["dtypes"]["adam_moments"])
        self.lr_fn = constant(o["lr"])
        self.mesh = Mesh(np.array(devices), ("data",))
        self.dp = len(devices)
        self.rows = self.dp * cell.mix["rows_per_chip"]
        self.seq_len = cell.mix["seq_len"]
        self.n_grad = weights.count(conf)
        self.to_mesh = NamedSharding(self.mesh, P("data"))

    def init_state(self, seed: int):
        from repro.optim import init_state
        from repro.train import TrainState
        opt = self.opt
        return weights.make(
            self.cell.conf, seed, NamedSharding(self.mesh, P()),
            extra=lambda p: TrainState(p, init_state(p, opt),
                                       jnp.zeros((), jnp.int32)))

    def rebuild(self, fault):
        """The launcher's reaction to a fault change: (step, plan)."""
        from repro.comms.fault import FaultState
        from repro.launch.train import rebuild_step
        fs = (FaultState(axis_size=self.dp) if fault is None else
              FaultState(axis_size=self.dp, straggler=fault[0], ell=fault[1]))
        return rebuild_step(self.model, self.mesh, self.opt, self.lr_fn, fs,
                            self.n_grad)

    def batch(self, seed: int, k: int) -> dict:
        """Step k's batch, made on the host and put onto the mesh."""
        return jax.device_put(
            workload.batch(seed, k, self.rows, self.seq_len,
                           self.cell.conf["vocab_size"]), self.to_mesh)


@dataclasses.dataclass
class Live:
    """What set-up hands to the window: the same step and state."""
    state: object
    step: Callable
    fault: object
    next_step: int
    readings: compare.Readings


def first_steps(prog: Program, sched: workload.Schedule, seed: int) -> Live:
    """Make the state from the seed and run the mix's first steps through
    the window's own call and feed; keep what the check compares."""
    state = prog.init_state(seed)
    step, fault = None, object()
    losses, mu = [], None
    for k, f in enumerate(sched.first()):
        if f != fault:
            with span("rebuild"):
                step, _ = prog.rebuild(f)
            fault = f
        with span("batch"):
            b = prog.batch(seed, k)
        with span("dispatch"):
            state, m = step(state, b)
        losses.append(float(m["loss"]))
        if k == 0:     # the gradient as the optimizer got it: mu / (1 - b1)
            mu = host_copy(state.opt_state["mu"])
    return Live(state, step, fault, len(losses),
                compare.Readings(losses, mu, host_copy(state.params),
                                 grad_scale=1.0 / (1.0 - prog.opt.b1)))


@dataclasses.dataclass
class Window:
    seconds: float = 0.0
    steps: int = 0
    tokens: int = 0
    failed: int = 0
    switches: list = dataclasses.field(default_factory=list)


def run_window(prog: Program, sched: workload.Schedule, live: Live,
               seed: int, seconds: float) -> Window:
    """Drive the step for `seconds`: a steady mix stops at the first step
    after that, a trace at the end of the cycle that is running then."""
    pending: collections.deque = collections.deque()
    losses: list = []
    switches: list = []
    k = live.next_step
    state, step, fault = live.state, live.step, live.fault
    t0 = time.perf_counter()

    def dispatch():
        nonlocal state, k
        with span("batch"):
            b = prog.batch(seed, k)
        with span("dispatch"):
            state, m = step(state, b)
        k += 1
        losses.append(m["loss"])
        return m

    for f, n in sched.window():
        done = 0
        if f != fault:
            with span("drain"):
                jax.block_until_ready(list(pending))
            pending.clear()
            t_change = time.perf_counter()
            with span("rebuild"):
                step, plan = prog.rebuild(f)
            fault = f
            m = dispatch()
            t_return = time.perf_counter()
            with span("drain"):
                m["loss"].block_until_ready()
            switches.append({
                "failover_s": time.perf_counter() - t_change,
                "host_s": t_return - t_change,
                "plan_s": None if plan is None else plan.gen_seconds})
            done = 1
        while n is None or done < n:
            if n is None and time.perf_counter() - t0 >= seconds:
                break
            pending.append(dispatch()["loss"])
            done += 1
            if len(pending) > LAG:
                with span("wait"):
                    pending.popleft().block_until_ready()
        if n is None or (f is not None and time.perf_counter() - t0 >= seconds):
            break
    with span("drain"):
        jax.block_until_ready(state)
    win = Window(seconds=time.perf_counter() - t0, steps=len(losses),
                 switches=switches)
    win.tokens = win.steps * prog.rows * prog.seq_len
    values = np.asarray(jax.device_get(losses), np.float64)
    win.failed = int(np.count_nonzero(~np.isfinite(values)))
    live.state = state
    return win


# ----------------------------------------------------------------------------
# the reference
# ----------------------------------------------------------------------------

def reference(cell: Cell, seed: int, steps: int, device, fp8: bool = False):
    """(Readings, parameters before step 1) of the plain reference over
    the first `steps` steps, on `device`, from its own weights."""
    ref = refs.module(cell.conf)
    conf, mix = cell.conf, cell.mix
    one = SingleDeviceSharding(device)
    params = weights.make(conf, seed, one)
    params0 = jax.device_get(params)
    rows = mix["dp"] * mix["rows_per_chip"]
    moments = [None] * len(jax.tree.leaves(params))
    losses, grad = [], None
    for k in range(steps):
        b = jax.device_put(workload.batch(seed, k, rows, mix["seq_len"],
                                          conf["vocab_size"]), one)
        loss, grads = ref.loss_and_grad(conf, params, b["tokens"],
                                        b["labels"], fp8=fp8)
        losses.append(float(loss))
        params, clipped = ref.adamw_step(
            conf["optimizer"], params, grads, moments, k + 1,
            conf["dtypes"]["params"], keep_clipped=k == 0)
        del grads
        if k == 0:
            grad = jax.tree.unflatten(jax.tree.structure(params), clipped)
    out = compare.Readings(losses, grad, jax.device_get(params))
    del params
    return out, params0


def free(tree) -> None:
    for a in jax.tree.leaves(tree):
        if isinstance(a, jax.Array) and not a.is_deleted():
            a.delete()


def memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None

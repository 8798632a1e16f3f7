"""Every cell's run, rehearsed on CPU devices at toy widths through the
functions `bench/run.py` calls; and run.py's refusals."""
import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import pytest

import jax

from conftest import CONFIGS, MIXES, ROOT, mix_cell, toy
from bench import counts, harness

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
PAIRS = [(m, c) for m in MIXES for c in CONFIGS]
V5E = counts.peaks("TPU v5 lite")     # the CPU has no row: rehearsal only
SEED = 2**31 + 4099                   # seeds run past 32 signed bits


def _run(cell, trace=False, seconds=0.5, **kw):
    from bench import run
    return run.run(cell, SEED, seconds, trace, jax.devices()[:cell.chips],
                   t_start=time.perf_counter(), peaks=V5E, **kw)


@pytest.mark.parametrize("name", CELLS)
def test_committed_cell_rehearsal(name):
    cell = toy(harness.load_cell(name))
    out = _run(cell)
    assert out["correct"], out["compared"]
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}


@pytest.mark.parametrize("mix,config", PAIRS)
def test_mix_rehearsal_is_correct_and_reports_its_metrics(mix, config):
    cell = mix_cell(mix, config)
    out = _run(cell)
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["count"] == cell.chips
    assert list(out)[-1] == "compared"
    assert set(out["compared"]) == set(cell.limits)


@pytest.mark.parametrize("mix", MIXES)
def test_traced_rehearsal_reads_per_layer_metrics(mix):
    """On the CPU the trace has no TPU planes: the device readers find
    nothing and stay silent; the host readers report."""
    cell = mix_cell(mix)
    out = _run(cell, trace=True)
    assert out["correct"]
    names = {m["name"] for m in cell.per_layer}
    assert set(out["metrics"]) <= names
    assert "step_mfu" in out["metrics"]
    if cell.mix["faults"]["kind"] == "trace":
        assert {"failover_host_s", "plan_ms"} <= set(out["metrics"])
    assert "device_idle" not in out["metrics"]
    assert out["device"]["window_s"] > 0


def test_fault_trace_window_holds_whole_cycles():
    cell = mix_cell("dp4.faults")
    from bench import workload
    prog = harness.Program(cell, jax.devices()[:4])
    sched = workload.Schedule(cell.mix, SEED)
    live = harness.first_steps(prog, sched, SEED)
    assert live.next_step == 1 + 4              # healthy, then 4 stragglers
    win = harness.run_window(prog, sched, live, SEED, 0.01)
    n = cell.mix["faults"]["steps_per_state"]
    assert len(win.switches) == 2 and win.steps == 2 * n
    assert win.switches[0]["plan_s"] is None           # to healthy
    assert win.switches[1]["plan_s"] is not None       # to degraded
    harness.free(live.state)


def test_same_seed_same_batches_and_faults():
    from bench import workload
    cell = mix_cell("dp4.faults")
    a, b = workload.Schedule(cell.mix, SEED), workload.Schedule(cell.mix, SEED)
    assert a.first() == b.first()
    wa, wb = a.window(), b.window()
    assert [next(wa) for _ in range(8)] == [next(wb) for _ in range(8)]
    x = workload.batch(SEED, 3, 4, 16, 1000)
    y = workload.batch(SEED, 3, 4, 16, 1000)
    z = workload.batch(SEED, 4, 4, 16, 1000)
    assert (x["tokens"] == y["tokens"]).all()
    assert (x["tokens"] != z["tokens"]).any()
    assert (x["labels"][:, :-1] == x["tokens"][:, 1:]).all()


def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "minicpm.dp1.step",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    got = _command(ROOT)
    assert got.returncode != 0
    assert got.stdout.strip() == ""
    assert "needs a TPU" in got.stderr


def test_run_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    got = _command(tmp_path, {"PYTHONPATH": ""})
    assert got.returncode != 0
    assert got.stdout.strip() == ""


def test_every_named_file_exists():
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).is_file()
    for w in SPEC["workloads"]:
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
        assert (ROOT / "bench" / "limits" / f"{w['name']}.json").is_file()
        assert harness.load_cell(w["name"]).per_layer
    for m in SPEC["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
    assert pathlib.Path(ROOT / "bench" / "peaks.json").is_file()

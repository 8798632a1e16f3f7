"""The check fails what it must: the float8 control in the program's place,
and each fault that a cell can have planted under the timed path, at toy
widths on CPU devices, under the cells' own limits."""
import time

import pytest

import jax

from conftest import CONFIGS, MIXES, mix_cell
from bench import compare, counts, faults, harness

SEEDS = [2**31 + 17, 977]
PAIRS = [(m, c) for m in MIXES for c in CONFIGS]


def _faults(cell):
    out = ["state_unchanged", "half_batch"]
    if cell.chips > 1:
        out.append("no_exchange")
    return out


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("mix,config", PAIRS)
def test_control_fails_and_program_passes(mix, config, seed):
    cell = mix_cell(mix, config)
    from bench import workload
    prog = harness.Program(cell, jax.devices()[:cell.chips])
    live = harness.first_steps(prog, workload.Schedule(cell.mix, seed), seed)
    harness.free(live.state)
    k = live.next_step
    ref, params0 = harness.reference(cell, seed, k, jax.devices()[0])
    ctl, _ = harness.reference(cell, seed, k, jax.devices()[0], fp8=True)
    ok, rows = compare.judge(compare.numbers(live.readings, ref, params0),
                             cell.limits)
    assert ok, rows
    ok, rows = compare.judge(compare.numbers(ctl, ref, params0), cell.limits)
    assert not ok, rows


@pytest.mark.parametrize("mix", MIXES)
def test_each_fault_fails_the_whole_run(mix):
    """The rest of a run, chip look skipped, the timed path broken."""
    from bench import run
    cell = mix_cell(mix)
    for fault in _faults(cell):
        with faults.planted(fault) as wrap:
            out = run.run(cell, SEEDS[0], 0.3, False,
                          jax.devices()[:cell.chips], wrap_model=wrap,
                          t_start=time.perf_counter(),
                          peaks=counts.peaks("TPU v5 lite"))
        assert not out["correct"], (fault, out["compared"])

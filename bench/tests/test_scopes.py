"""Device time by the program's named stages (bench/scopes.py): the join
rehearsed on CPU devices at toy widths, and the reduction of a trace
recorded on a TPU v5e joined with the program the chip compiled."""
import gzip
import pathlib

import pytest

import jax

from conftest import mix_cell
from bench import run, scopes, xplane

DATA = pathlib.Path(__file__).resolve().parent / "data"
# internlm2.dp4.degraded (internlm2-1.8b, dp4.degraded: member 1 at l=2) on
# four chips, 0.9 s of a traced window, cut to the lines the reduction reads,
# and the compiled text of the step it ran (source file paths made relative)
DP4 = DATA / "internlm2_dp4_degraded.xplane.pb"
DP4_HLO = DATA / "internlm2_dp4_degraded.hlo.txt.gz"


@pytest.fixture(scope="module")
def degraded():
    cell = mix_cell("dp4.degraded", "internlm2-1.8b")
    return cell, scopes.program_map(scopes.compiled_text(cell,
                                                        jax.devices()[:4]))


def test_window_step_names_optcc_stages(degraded):
    _, pmap = degraded
    paths = set(pmap.scope.values())
    for stage in ("flatten", "S3", "S1/hop0", "S1/hop1", "S4/hop0",
                  "S4/hop1", "S2", "loss"):
        assert f"grad_sync/{stage}" in paths, stage
    assert {"model/forward", "model/backward", "optimizer"} <= paths


class _Window:
    steps, switches = 4, []


def _ctx(cell, op_s_per_chip):
    chips = [xplane.Chip(i, 1.0, 0.0, op_s, []) for i, op_s in
             enumerate(op_s_per_chip)]
    return {"cell": cell, "window": _Window(),
            "trace": xplane.Reduced(1.0, chips, {}), "chips": len(chips)}


def test_stage_times_on_a_synthetic_trace(degraded):
    """Every instruction of the toy step takes 1 ms a step on chip 0 and
    2 ms on chip 1: per step, the mean over chips."""
    cell, pmap = degraded
    smap = pmap.scope
    op_s = {pmap.label[n]: 4e-3 for n in smap}
    op_s["mystery.1 f32[8] fusion"] = 4e-3
    ctx = _ctx(cell, [op_s, {k: 2 * v for k, v in op_s.items()}])
    per_step = scopes.per_step(ctx)
    count = {p: sum(1 for s in smap.values() if s.startswith(p))
             for p in ("grad_sync", "grad_sync/S", "optimizer")}
    assert scopes.total(per_step, "grad_sync") == pytest.approx(
        1.5e-3 * count["grad_sync"])
    assert run.metric_reader("optimizer_s")(ctx) == pytest.approx(
        1.5e-3 * count["optimizer"])
    assert scopes.total(per_step, "grad_sync/S1", "grad_sync/S2",
                        "grad_sync/S3", "grad_sync/S4") == pytest.approx(
        1.5e-3 * count["grad_sync/S"])
    assert per_step[scopes.NOT_IN_PROGRAM] == pytest.approx(1.5e-3)


def test_a_window_that_switched_programs_reads_nothing(degraded):
    cell, pmap = degraded
    ctx = _ctx(cell, [{label: 1e-3 for label in pmap.label.values()}])
    ctx["window"] = type("W", (), {"steps": 4, "switches": [{}]})()
    assert scopes.per_step(ctx) is None
    assert run.metric_reader("optimizer_s")(ctx) is None


def test_a_trace_of_another_program_reads_nothing(degraded):
    """Over 1% of op time under the program's names with other shapes:
    the trace ran another program, so no stage time is read."""
    cell, pmap = degraded
    op_s = {label: 1e-3 for label in pmap.label.values()}
    odd = {label.replace("f32[", "bf16[", 1): 1.0 for label in op_s
           if " f32[" in label}
    assert odd
    assert scopes.per_step(_ctx(cell, [dict(op_s, **odd)])) is None
    assert scopes.per_step(_ctx(cell, [op_s])) is not None


def test_no_device_planes_read_nothing(degraded):
    cell, _ = degraded
    assert run.metric_reader("optimizer_s")(_ctx(cell, [])) is None


@pytest.fixture(scope="module")
def dp4():
    red = xplane.reduce(DP4)
    with gzip.open(DP4_HLO, "rt") as f:
        pmap = scopes.program_map(f.read())
    out: dict = {}
    for chip in red.chips:
        for path, s in scopes.attribute(chip.op_s, pmap).items():
            out[path] = out.get(path, 0.0) + s / len(red.chips)
    return red, pmap, out


def test_recorded_dp4_trace_is_all_in_scope(dp4):
    red, pmap, per = dp4
    assert len(red.chips) == 4 and red.window_s == pytest.approx(0.9)
    busy = sum(per.values())
    assert per.get(scopes.NOT_IN_PROGRAM, 0.0) == 0.0
    assert per.get(scopes.UNATTRIBUTED, 0.0) < 0.01 * busy
    assert sum(scopes.mismatched(c.op_s, pmap) for c in red.chips) == 0.0
    # inheritance places 28.9% of the op time: above all the loops XLA
    # made to write the subring's accumulator, and their slices
    own = sum(scopes.unscoped_own(c.op_s, pmap) for c in red.chips)
    assert own / len(red.chips) / busy == pytest.approx(0.2894, abs=1e-4)
    assert {"model/forward", "model/backward", "optimizer",
            "grad_sync/flatten", "grad_sync/S3", "grad_sync/S1/hop0",
            "grad_sync/S1/hop1", "grad_sync/S4/hop0", "grad_sync/S4/hop1",
            "grad_sync/S2", "grad_sync/loss"} <= set(per)


def test_recorded_dp4_stage_sums(dp4):
    """The sync's stages add up to the sync less the loss's psum, and read
    what the chip gave (mean over the four chips, in the 0.9 s cut)."""
    _, _, per = dp4
    sync = scopes.total(per, "grad_sync")
    link = scopes.total(per, "grad_sync/S3", "grad_sync/S2")
    ring = scopes.total(per, "grad_sync/S1", "grad_sync/S4")
    copy = scopes.total(per, "grad_sync/flatten", "grad_sync/unflatten")
    loss = scopes.total(per, "grad_sync/loss")
    assert link + ring + copy + loss == pytest.approx(sync)
    assert sync == pytest.approx(0.7138072675, rel=1e-6)
    assert link == pytest.approx(0.06298704025, rel=1e-6)
    assert ring == pytest.approx(0.58901485, rel=1e-6)
    assert copy == pytest.approx(0.0161583235, rel=1e-6)
    assert scopes.total(per, "optimizer") == pytest.approx(0.0651374675,
                                                           rel=1e-6)

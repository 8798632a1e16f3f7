"""The trace reduction (bench/xplane.py) on small traces recorded on a
TPU v5e by `bench/run.py --trace 1 --keep-trace`, cut to a short range of
the window (events outside it dropped, the `window` span cut to it)."""
import pathlib

import numpy as np
import pytest

from bench import xplane

DATA = pathlib.Path(__file__).resolve().parent / "data"
DP1 = DATA / "minicpm_dp1_step.xplane.pb"      # one chip, 0.4 s of steps


@pytest.fixture(scope="module")
def dp1():
    return xplane.reduce(DP1)


def _raw_ops(path):
    """(name, start, end) of every device op, read without the reduction."""
    from jax.profiler import ProfileData
    out = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    out[plane.name] = [(e.name, e.start_ns, e.end_ns)
                                       for e in line.events]
    return out


def test_dp1_trace_busy_idle_and_spans(dp1):
    assert dp1.window_s == pytest.approx(0.4)
    (chip,) = dp1.chips
    assert 0.99 * dp1.window_s < chip.busy_s <= dp1.window_s
    assert chip.collective_s == 0.0             # one chip: no sync
    assert {"window", "batch", "dispatch"} <= set(dp1.spans)
    idle = sum(s for s, _ in chip.gaps)
    assert idle == pytest.approx(dp1.window_s - chip.busy_s, abs=1e-9)
    assert all(label in xplane.SPANS + ("other",) for _, label in chip.gaps)


def test_dp1_busy_is_the_union_of_ops(dp1):
    """Busy time against a millisecond-grid count of covered instants."""
    (ops,) = _raw_ops(DP1).values()
    w0, w1 = dp1.spans["window"][0]
    grid = np.zeros(int((w1 - w0) // 1e5) + 1, bool)     # 0.1 ms cells
    for _, s, e in ops:
        s, e = max(s, w0), min(e, w1)
        if e > s:
            grid[int((s - w0) // 1e5):int(np.ceil((e - w0) / 1e5))] = True
    covered = grid.sum() * 1e-4
    assert dp1.chips[0].busy_s == pytest.approx(covered, abs=2e-3)


def test_dp1_top_ops_leave_out_loops(dp1):
    (chip,) = dp1.chips
    assert not any(k.endswith(" while") for k in chip.op_s)
    top = max(chip.op_s, key=chip.op_s.get)
    assert top.split()[-1] == "fusion"
    # ops inside loops and the loops' bodies never add up past the window
    assert sum(chip.op_s.values()) <= dp1.window_s * 1.001


@pytest.mark.parametrize("text,want", [
    ("%fusion.506 = bf16[2304,122753]{0,1:T(8,128)(2,1)} fusion(f32[256] "
     "%a), kind=kOutput", ("fusion.506", "fusion", "bf16[2304,122753]")),
    ("%collective-permute-start.3 = (f32[1024]{0:T(1024)}, f32[1024]{0}, "
     "u32[]{:S(2)}) collective-permute-start(f32[1024]{0} %x), "
     "source_target_pairs={{0,1}}",
     ("collective-permute-start.3", "collective-permute-start",
      "(f32[1024], f32[1024], u32[])")),
    ("%psum = bf16[8]{0} all-reduce(bf16[8]{0} %g), replica_groups={}",
     ("psum", "all-reduce", "bf16[8]")),
    ("jit_step(123)", ("jit_step(123)", "", "")),
])
def test_parse_op(text, want):
    assert xplane.parse_op(text) == want


def test_collective_opcodes():
    for op in ("all-reduce", "all-reduce-start", "collective-permute-done",
               "all-gather", "reduce-scatter", "all-to-all"):
        assert xplane.is_collective(op)
    for op in ("fusion", "copy-start", "while", "custom-call", ""):
        assert not xplane.is_collective(op)

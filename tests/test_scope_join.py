"""The benchmark's join of a device trace with the compiled program's op
metadata (bench/scopes.py), on synthetic operations and HLO text; and the
scope names the benchmark reads against those the program uses."""
import pytest

from bench import scopes
from repro.obs import scopes as program_scopes

HLO = """\
HloModule jit_step, entry_computation_layout={()->f32[8]{0}}

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %fusion.2 = f32[8]{0:T(128)} fusion(f32[8]{0} %gte), kind=kLoop, calls=%fc.2, metadata={op_name="jit(step)/shard_map/model/transpose(jvp(dense))/while/body/mul" stack_frame_id=3}
  %iota.8 = s32[8]{0} iota(), iota_dimension=0
  ROOT %tuple.9 = (s32[8]{0}) tuple(%iota.8)
}

%copy_loop (q: (u32[], f32[8])) -> (u32[], f32[8]) {
  %q = (u32[], f32[8]{0}) parameter(0)
  %dynamic-slice.6 = f32[4]{0} dynamic-slice(f32[8]{0} %q, u32[] %q), dynamic_slice_sizes={4}
  ROOT %tuple.7 = (f32[4]{0}) tuple(%dynamic-slice.6)
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0), metadata={op_name="state[0]"}
  %copy-start.1 = (f32[8]{0:T(128)S(1)}, f32[8]{0:T(128)}, u32[]{:S(2)}) copy-start(%a)
  %copy-done.1 = f32[8]{0:T(128)S(1)} copy-done(%copy-start.1)
  %fusion.1 = f32[8]{0:T(128)} fusion(%copy-done.1), kind=kOutput, calls=%fc.1, metadata={op_name="jit(step)/shard_map/model/jvp(dense)/dot_general"}
  %while.1 = (s32[], f32[8]{0}) while(%t), condition=%cond, body=%body, metadata={op_name="jit(step)/shard_map/model/transpose(jvp(dense))/while"}
  %collective-permute-start.1 = (f32[8]{0}, f32[8]{0}, u32[]{:S(2)}) collective-permute-start(%fusion.1), source_target_pairs={{0,1}}, metadata={op_name="jit(step)/shard_map/grad_sync/S1/hop0/ppermute"}
  %collective-permute-done.1 = f32[8]{0} collective-permute-done(%collective-permute-start.1), metadata={op_name="jit(step)/shard_map/grad_sync/S1/hop0/ppermute"}
  %while.2 = (u32[], f32[8]{0}) while(%collective-permute-done.1), condition=%cond, body=%copy_loop
  %psum.3 = f32[] all-reduce(f32[] %l), replica_groups={}, to_apply=%add, metadata={op_name="jit(step)/shard_map/grad_sync/loss/psum"}
  %fusion.3 = f32[8]{0} fusion(%collective-permute-done.1), kind=kLoop, calls=%fc.3, metadata={op_name="jit(step)/shard_map/optimizer/sub"}
  %copy.9 = f32[8]{0} copy(%fusion.3)
  %add.4 = s32[] add(s32[] %s, s32[] %one), metadata={op_name="jit(step)/add"}
  ROOT %tuple.5 = (f32[8]{0}, s32[]) tuple(%copy.9, %add.4)
}
"""


@pytest.fixture(scope="module")
def pmap():
    return scopes.program_map(HLO)


@pytest.fixture(scope="module")
def smap(pmap):
    return pmap.scope


def test_scope_of_op_names():
    assert scopes.scope_of("jit(step)/shard_map/model/jvp(dense)/dot_general") \
        == "model/forward"
    assert scopes.scope_of(
        "jit(step)/shard_map/model/transpose(jvp(dense))/while/body/mul") \
        == "model/backward"
    assert scopes.scope_of("jit(step)/shard_map/grad_sync/S4/hop1/ppermute") \
        == "grad_sync/S4/hop1"
    # the last part names the primitive, never a scope
    assert scopes.scope_of("jit(step)/shard_map/grad_sync/psum/psum") \
        == "grad_sync/psum"
    assert scopes.scope_of("jit(step)/shard_map/grad_sync/S3/jit(_where)/"
                           "select_n") == "grad_sync/S3"
    assert scopes.scope_of("jit(step)/shard_map/optimizer/sub") == "optimizer"
    assert scopes.scope_of("jit(step)/add") is None
    assert scopes.scope_of("") is None


def test_scope_map_reads_op_names_and_inherits(smap):
    assert smap["fusion.1"] == "model/forward"
    assert smap["fusion.2"] == "model/backward"
    assert smap["collective-permute-start.1"] == "grad_sync/S1/hop0"
    assert smap["psum.3"] == "grad_sync/loss"
    # compiler-made copies take their consumer's scope ...
    assert smap["copy-start.1"] == smap["copy-done.1"] == "model/forward"
    # ... or, feeding only the output, their producer's
    assert smap["copy.9"] == "optimizer"
    assert smap["add.4"] == ""
    # ... or, in a loop body with neither, the scope of the loop that runs it
    assert smap["iota.8"] == "model/backward"
    assert smap["dynamic-slice.6"] == smap["while.2"] == "grad_sync/S1/hop0"


def test_join_counts_the_unscoped_and_the_unknown(pmap):
    """Op labels as bench/xplane.py gives them (loops and calls already
    left out): an instruction outside every scope is unattributed, one the
    text does not name is not in the program."""
    op_s = {"fusion.1 f32[8] fusion": 1.0,
            "fusion.2 f32[8] fusion": 2.0,
            "collective-permute-done.1 f32[8] collective-permute-done": 0.5,
            "collective-permute-start.1 (f32[8], f32[8], u32[]) "
            "collective-permute-start": 0.25,
            "copy-done.1 f32[8] copy-done": 0.125,
            "fusion.3 f32[8] fusion": 0.75,
            "add.4 s32[] add": 0.0625,                       # no scope
            "fusion.77 f32[8] fusion": 0.03125,              # not in the map
            "jit_step(123)": 0.015625}                       # not an op
    got = scopes.attribute(op_s, pmap)
    assert got == {"model/forward": 1.125, "model/backward": 2.0,
                   "grad_sync/S1/hop0": 0.75, "optimizer": 0.75,
                   scopes.UNATTRIBUTED: 0.0625,
                   scopes.NOT_IN_PROGRAM: 0.03125 + 0.015625}
    assert scopes.total(got, "grad_sync") == 0.75
    assert scopes.total(got, "grad_sync/S1", "grad_sync/S4") == 0.75
    assert scopes.total(got, "model") == 3.125
    assert scopes.total(got, "grad_sync/S2") is None
    assert scopes.total(got, "model/f") is None          # whole parts only
    # copy-done.1 took its scope from its consumer; add.4 found none
    assert scopes.unscoped_own(op_s, pmap) == 0.125 + 0.0625
    assert scopes.mismatched(op_s, pmap) == 0.0


def test_labels_match_the_trace_and_catch_another_program(pmap):
    assert pmap.label["fusion.1"] == "fusion.1 f32[8] fusion"
    assert pmap.label["collective-permute-start.1"] == (
        "collective-permute-start.1 (f32[8], f32[8], u32[]) "
        "collective-permute-start")
    assert pmap.own["copy.9"] == "" and pmap.scope["copy.9"] == "optimizer"
    other = {"fusion.1 bf16[8] fusion": 1.0,          # another shape
             "fusion.3 f32[8] add": 0.5,               # another opcode
             "fusion.2 f32[8] fusion": 0.25}
    assert scopes.mismatched(other, pmap) == 1.5


def test_operand_lists_skip_shapes_and_layouts():
    instrs, callers = scopes._parse(HLO)
    assert instrs["copy-done.1"][:2] == ("", ["copy-start.1"])
    assert instrs["fusion.2"][1] == ["gte"]
    assert instrs["tuple.5"][:2] == ("", ["copy.9", "add.4"])
    assert callers["copy_loop"] == "while.2"


def test_the_benchmark_reads_the_names_the_program_uses():
    assert scopes.TOP == program_scopes.TOP
    assert set(scopes.SYNC) == set(program_scopes.SYNC)
    assert scopes.HOP.match(program_scopes.hop(12))

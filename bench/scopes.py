"""Device time by the program's named stages: the trace joined with the
compiled program's own op metadata.

The train step wraps its work in named scopes (`model`, `grad_sync` with
OptCC's stages inside it, `optimizer`). A scope lands in the `op_name` of
every HLO instruction it produced, and the profiler trace names each
operation by its instruction, so one map, instruction -> op_name, read from
the compiled program's text, attributes every operation's time to a scope
path. An instruction the compiler made without a scope takes one from
the instructions around it (`program_map`); the share of op time that
needed this is logged beside the share left without a scope after it. The
names below are the benchmark's own copy of those the program uses; the
benchmark imports none of them.

In a traced run, after the window, `per_step(ctx)` builds the window's step
again, lowers it for the live state's and batch's shapes and shardings and
compiles it (with the op metadata in the persistent cache's key, so the
names are this program's), and sums each chip's operation time (loops
and calls are left out by `bench/xplane.py`) by scope path, in seconds
per step, the mean over chips. Time whose instruction has no scope is
`UNATTRIBUTED`; time of an operation the compiled text does not name is
`NOT_IN_PROGRAM`. A window that switched programs, a trace with no device
operations, a program that names no scope, or a trace whose operations
differ from the compiled program's (over 1% of op time under a name the
text lacks or with another shape or opcode) gives None.
"""
from __future__ import annotations

import dataclasses
import re
import sys
import time

from bench.xplane import parse_op

TOP = ("model", "grad_sync", "optimizer")
SYNC = ("flatten", "S3", "S1", "S4", "S2", "unflatten", "loss", "psum")
HOP = re.compile(r"hop\d+$")
BACKWARD = "transpose("
UNATTRIBUTED = "(unattributed)"
NOT_IN_PROGRAM = "(not in the program)"
MISMATCH_LIMIT = 0.01     # share of op time the trace may hold unmatched

OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
OPERAND = re.compile(r"%([\w.\-]+)")
COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)")
CALLED = re.compile(r"(?:body|condition|calls|to_apply)=%([\w.\-]+)")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class ProgramMap:
    """What the join reads of a compiled module's text, by instruction."""
    scope: dict     # scope path, inherited where its own op_name names none
    own: dict       # scope path its own op_name names ("" for none)
    label: dict     # "instruction shape opcode", as bench/xplane.py labels


def _parse(hlo_text: str):
    """({instruction: (op_name, operands, computation, label)},
    {computation: the instruction that calls it})."""
    instrs, callers = {}, {}
    comp = None
    for line in hlo_text.splitlines():
        m = INSTRUCTION.match(line)
        if not m:
            if line[:1] not in ("", " ") and line.rstrip().endswith("{"):
                comp = COMPUTATION.match(line).group(1)
            continue
        name = m.group(1)
        o = OP_NAME.search(line, m.end())
        _, opcode, shape = parse_op(line.strip().removeprefix("ROOT "))
        instrs[name] = (o.group(1) if o else "",
                        OPERAND.findall(_operand_list(line, m.end())), comp,
                        f"{name} {shape} {opcode}")
        for callee in CALLED.findall(line):
            callers.setdefault(callee, name)
    return instrs, callers


def _operand_list(line: str, start: int) -> str:
    """The text inside the parentheses after the opcode."""
    rest = line[start:]
    if rest.startswith("("):                 # a tuple shape: skip it
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        rest = rest[i + 1:]
    else:
        rest = rest.partition(" ")[2]
    i = rest.find("(")
    if i < 0:
        return ""
    depth = 0
    for j in range(i, len(rest)):
        depth += rest[j] == "("
        depth -= rest[j] == ")"
        if depth == 0:
            return rest[i + 1:j]
    return rest[i + 1:]


def program_map(hlo_text: str) -> ProgramMap:
    """The scope of every instruction of a compiled module's text.

    An instruction whose op_name names no scope (a copy, layout change or
    loop-carry initialisation the compiler added, or a constant JAX hoisted
    out of a loop) takes the scope of the nearest instruction that consumes
    it and has one; failing that, of the nearest that produces it (a copy
    of a result to the program's output); failing that, the scope of the
    instruction that runs its computation (a loop the compiler made to
    copy a large buffer in pieces)."""
    instrs, callers = _parse(hlo_text)
    operands = {name: ops for name, (_, ops, _, _) in instrs.items()}
    users: dict = {}
    for name, ops in operands.items():
        for o in ops:
            users.setdefault(o, []).append(name)
    own = {name: scope_of(op) or "" for name, (op, *_) in instrs.items()}
    out: dict = {}

    def resolve(name: str, depth: int = 0) -> str:
        if name not in out:
            found = (own[name] or _nearest(name, users, own)
                     or _nearest(name, operands, own))
            caller = callers.get(instrs[name][2])
            if not found and caller in instrs and depth < 16:
                found = resolve(caller, depth + 1)
            out[name] = found
        return out[name]

    for name in instrs:
        resolve(name)
    return ProgramMap(out, own, {n: i[3] for n, i in instrs.items()})


def _nearest(name: str, edges: dict, own: dict) -> str:
    """The scope of the first instruction with one, breadth first along
    `edges` from `name`."""
    seen, frontier = {name}, [name]
    while frontier:
        nxt = []
        for n in frontier:
            for m in edges.get(n, ()):
                if m in seen or m not in own:
                    continue
                if own[m]:
                    return own[m]
                seen.add(m)
                nxt.append(m)
        frontier = nxt
    return ""


def scope_of(op_name: str):
    """The scope path of an op_name, or None outside every scope.

    `model` splits into `model/forward` and `model/backward`; under
    `grad_sync` the path follows the sync's own scopes down to `hop<t>`."""
    parts = op_name.split("/")
    for i, p in enumerate(parts):
        if p in TOP:
            break
    else:
        return None
    if p == "model":
        return "model/backward" if BACKWARD in op_name else "model/forward"
    path = [p]
    for q in parts[i + 1:-1]:         # the last part names the primitive
        if q in SYNC or HOP.match(q):
            path.append(q)
        else:
            break
    return "/".join(path)


def attribute(op_s: dict, pmap: ProgramMap) -> dict:
    """{scope path: seconds} of one chip's {op label: seconds}, where a
    label is "instruction shape opcode" (`bench/xplane.py`, which leaves
    loops and calls out). UNATTRIBUTED holds the time of instructions
    outside every scope, NOT_IN_PROGRAM that of operations the map does
    not name."""
    out: dict = {}
    for label, s in op_s.items():
        path = pmap.scope.get(label.split(" ", 1)[0])
        key = NOT_IN_PROGRAM if path is None else path or UNATTRIBUTED
        out[key] = out.get(key, 0.0) + s
    return out


def unscoped_own(op_s: dict, pmap: ProgramMap) -> float:
    """Seconds of one chip's operations whose own op_name names no scope:
    what `program_map`'s inheritance placed, or left UNATTRIBUTED."""
    return sum(s for label, s in op_s.items()
               if pmap.own.get(label.split(" ", 1)[0]) == "")


def mismatched(op_s: dict, pmap: ProgramMap) -> float:
    """Seconds of one chip's operations that the compiled program names
    with another shape or opcode: a sign of another program."""
    out = 0.0
    for label, s in op_s.items():
        own = pmap.label.get(label.split(" ", 1)[0])
        if own is not None and own != label:
            out += s
    return out


def total(per_step: dict, *prefixes: str):
    """Seconds under any of the scope paths `prefixes` (a path and all
    below it); None where none of them appears."""
    got = [s for path, s in per_step.items()
           if any(path == p or path.startswith(p + "/") for p in prefixes)]
    return sum(got) if got else None


def compiled_text(cell, devices) -> str:
    """The cell's window step compiled again for the shapes and shardings
    the window ran it on."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from bench import harness, workload
    prog = harness.Program(cell, devices)
    step, _ = prog.rebuild(workload.Schedule(cell.mix, 0).first()[-1])
    rep = NamedSharding(prog.mesh, P())
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep),
        jax.eval_shape(lambda: prog.init_state(0)))
    tok = jax.ShapeDtypeStruct((prog.rows, prog.seq_len), jnp.int32,
                               sharding=prog.to_mesh)
    # The persistent cache's key leaves op metadata out, so a program that
    # differs only in its scopes would load another's executable and its
    # stale op names; with the metadata in the key this compile is either
    # fresh or this very program's. Instruction names do not depend on it.
    key = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, key)
    jax.config.update(key, True)
    try:
        lowered = step.lower(state, {"tokens": tok, "labels": tok})
        return lowered.compile().as_text()
    finally:
        jax.config.update(key, before)


_memo: dict = {}


def _entry(ctx) -> dict:
    if _memo.get("trace") is not ctx["trace"]:
        _memo.clear()
        _memo["trace"] = ctx["trace"]
    return _memo


def compile_counter(ctx):
    """The program's compile counter (`repro.obs.compiles`) as it stood
    when the window had closed, before this module compiles anything; None
    where the program has no counter or it counted nothing."""
    m = _entry(ctx)
    if "counter" not in m:
        try:
            from repro.obs import compiles
            snap = compiles.snapshot()
        except ImportError:
            snap = None
        m["counter"] = snap if snap and snap["compiles"] else None
    return m["counter"]


def per_step(ctx):
    """{scope path: device seconds per step, the mean over chips} of the
    traced window, UNATTRIBUTED included; None where it cannot be read."""
    m = _entry(ctx)
    if "per_step" not in m:
        compile_counter(ctx)
        m["per_step"] = _per_step(ctx)
    return m["per_step"]


def _per_step(ctx):
    red, win = ctx["trace"], ctx["window"]
    if not red.chips or win.steps == 0:
        return None
    if win.switches:
        log(f"scopes: the window ran {len(win.switches) + 1} programs; "
            "no stage times")
        return None
    import jax
    t0 = time.perf_counter()
    cell = ctx["cell"]
    pmap = program_map(compiled_text(cell, jax.devices()[:cell.chips]))
    out: dict = {}
    own = odd = 0.0
    for chip in red.chips:
        for path, s in attribute(chip.op_s, pmap).items():
            out[path] = out.get(path, 0.0) + s
        own += unscoped_own(chip.op_s, pmap)
        odd += mismatched(chip.op_s, pmap)
    busy = sum(out.values()) or 1.0
    share = {k: 100 * v / busy for k, v in
             (("own", own), ("left", out.get(UNATTRIBUTED, 0.0)),
              ("absent", out.get(NOT_IN_PROGRAM, 0.0)), ("odd", odd))}
    per = len(red.chips) * win.steps
    out = {path: s / per for path, s in out.items()}
    log(f"scopes: joined {len(pmap.scope)} instructions in "
        f"{time.perf_counter() - t0:.1f} s; op time {busy / per:.6f} s a "
        f"step: {share['own']:.3f}% without a scope of its own, "
        f"{share['left']:.3f}% outside every scope after inheritance, "
        f"{share['absent']:.3f}% not in the program, {share['odd']:.3f}% "
        "with another shape or opcode")
    for path, s in sorted(out.items(), key=lambda kv: -kv[1])[:24]:
        log(f"  {path:32s} {s:.6f} s a step")
    if share["absent"] + share["odd"] > 100 * MISMATCH_LIMIT:
        log("scopes: the trace's operations are not the compiled "
            "program's; no stage times")
        return None
    if not set(out) - {UNATTRIBUTED, NOT_IN_PROGRAM}:
        log("scopes: the program names no scope")
        return None
    return out

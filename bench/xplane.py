"""Reduce a profiler trace (`.xplane.pb`) to what the per-layer metrics read.

The JAX profiler writes one plane per TPU chip (`/device:TPU:<i>`), whose
`XLA Ops` line holds every operation that ran on it with its start and
duration, and a host plane (`/host:CPU`) whose threads hold the
TraceAnnotations of the benchmark's own spans (`window`, `batch`,
`dispatch`, `rebuild`, `drain`, `wait`). Both are on one clock, counted
from the start of the trace. An operation's event name is its HLO text,
`%fusion.506 = bf16[2304,122753]{0,1:T(8,128)(2,1)} fusion(...)`; a loop
(`while`) or call appears beside the operations of its body.

`reduce(path)` gives per chip: the busy time (the union of its operation
intervals inside the `window` span), the time in collective operations,
the time per operation name, and the idle gaps, each labelled with the
host span that covers most of it.
"""
from __future__ import annotations

import dataclasses
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPANS = ("batch", "dispatch", "rebuild", "drain", "wait")
WINDOW = "window"
COLLECTIVE = re.compile(
    r"^(all-reduce|collective-permute|all-gather|reduce-scatter|all-to-all"
    r"|collective-broadcast)(-start|-done)?$")
CONTAINERS = ("while", "call", "conditional")   # their bodies are listed too


@dataclasses.dataclass
class Chip:
    index: int
    busy_s: float
    collective_s: float
    op_s: dict              # "instruction shape opcode" -> seconds
    gaps: list              # (seconds, host span name), longest first


@dataclasses.dataclass
class Reduced:
    window_s: float
    chips: list
    spans: dict             # host span name -> [(start_ns, end_ns)]


def _union(intervals: list) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap(a0, a1, spans: list) -> float:
    return sum(max(0.0, min(a1, e) - max(a0, s)) for s, e in spans)


def parse_op(text: str) -> tuple[str, str, str]:
    """(instruction, opcode, result shape without layouts) of an event name
    that is HLO text; (text, "", "") for any other name."""
    m = re.match(r"%?([\w.\-]+) = ", text)
    if not m:
        return text, "", ""
    rest = text[m.end():]
    if rest.startswith("("):               # a tuple: skip to its close
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        shape, rest = rest[:i + 1], rest[i + 1:]
    else:
        shape, _, rest = rest.partition(" ")
    op = re.match(r"\s*([\w\-]+)\(", rest)
    return (m.group(1), op.group(1) if op else "",
            re.sub(r"\{[^}]*\}", "", shape))


def is_collective(opcode: str) -> bool:
    return COLLECTIVE.match(opcode) is not None


def reduce(path: str) -> Reduced:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    ops: dict = {}
    spans: dict = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.setdefault(int(m.group(1)), []).extend(
                        (e.name, e.start_ns, e.end_ns) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in SPANS or e.name == WINDOW:
                        spans.setdefault(e.name, []).append(
                            (e.start_ns, e.end_ns))
    if WINDOW not in spans:
        raise ValueError(f"{path}: no '{WINDOW}' span in the host trace")
    w0, w1 = spans[WINDOW][0]
    chips = []
    for idx in sorted(ops):
        inside = [(n, max(s, w0), min(e, w1)) for n, s, e in ops[idx]
                  if e > w0 and s < w1]
        busy = _union([(s, e) for _, s, e in inside])
        op_s: dict = {}
        coll = 0.0
        for n, s, e in inside:
            name, opcode, shape = parse_op(n)
            if is_collective(opcode):
                coll += (e - s) * 1e-9
            if opcode not in CONTAINERS:
                label = f"{name} {shape} {opcode}".strip()
                op_s[label] = op_s.get(label, 0.0) + (e - s) * 1e-9
        gaps, t = [], w0
        for s, e in busy + [[w1, w1]]:
            if s > t:
                label = max(SPANS, key=lambda k: _overlap(t, s, spans.get(k, [])))
                if _overlap(t, s, spans.get(label, [])) == 0:
                    label = "other"
                gaps.append(((s - t) * 1e-9, label))
            t = max(t, e)
        chips.append(Chip(idx, sum(e - s for s, e in busy) * 1e-9, coll, op_s,
                          sorted(gaps, reverse=True)))
    return Reduced((w1 - w0) * 1e-9, chips, spans)

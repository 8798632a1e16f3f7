"""Run one benchmark cell once on the chips of this machine.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in BENCHMARK.json and its configuration, traffic mix and
limits under bench/ by name. Set-up (counted in `setup_s`) makes the train
state from the seed on the device, builds the step and runs the mix's first
steps; the window then runs the program for `--seconds`; the check runs the
plain reference over the same first steps and compares. The last line of
stdout is one JSON object: `correct`, `attempted`, `failed`, `metrics`,
`device`, `breakdown` (traced runs) and `compared`. With `--trace 0` the
metrics are the cell's end-to-end ones, with `--trace 1` its per-layer
ones, read from a profiler trace of the window.

Exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for. The compile cache is `.jax_cache/` in the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced window's .xplane.pb to this path")
    return ap.parse_args(argv)


def metric_reader(name: str):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def main(argv=None) -> int:
    args = parse(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    try:
        from bench import harness
        from repro.launch.cache import enable_compile_cache
    except ImportError as e:
        log(f"bench/run.py needs the repository beside it: {e}")
        return 2
    cell = harness.load_cell(args.workload)
    enable_compile_cache()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"bench/run.py needs a TPU; JAX found {devices[0].platform} "
            f"({devices[0].device_kind})")
        return 2
    if len(devices) < cell.chips:
        log(f"{cell.name} needs {cell.chips} chips; {len(devices)} visible")
        return 2
    result = run(cell, args.seed, args.seconds, bool(args.trace),
                 devices[:cell.chips], keep_trace=args.keep_trace)
    print(json.dumps(result), flush=True)
    return 0


def run(cell, seed: int, seconds: float, trace: bool, devices,
        keep_trace=None, wrap_model=None, t_start: float = T_START,
        peaks=None) -> dict:
    """Everything after the device check; returns the result object.
    `peaks` replaces the peak table's row for the device (CPU rehearsals
    only: the CPU has none)."""
    import jax
    from bench import compare, counts, harness, workload, xplane
    dev = devices[0]
    log(f"{cell.name}: seed {seed}, {seconds} s, trace {int(trace)}, on "
        f"{len(devices)} x {dev.device_kind} ({dev.platform})")
    prog = harness.Program(cell, devices, wrap_model=wrap_model)
    sched = workload.Schedule(cell.mix, seed)
    live = harness.first_steps(prog, sched, seed)
    k_first = live.next_step
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s: {prog.n_grad} parameters, first "
        f"{k_first} steps, losses {live.readings.losses}")

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir)
    try:
        with jax.profiler.TraceAnnotation("window"):
            win = harness.run_window(prog, sched, live, seed, seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    log(f"window {win.seconds:.4f} s: {win.steps} steps, "
        f"{win.seconds / max(win.steps, 1):.4f} s a step on average, "
        f"{len(win.switches)} switches")
    for i, s in enumerate(win.switches):
        log(f"  switch {i}: failover {s['failover_s']:.4f} s, host "
            f"{s['host_s']:.4f} s, plan {s['plan_s']}")
    peak = harness.memory_peak(devices)
    harness.free(live.state)
    del live.state, live.step

    metrics: dict = {}
    breakdown = None
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if not trace:
        values = {"tokens_per_s": win.tokens / win.seconds,
                  "setup_s": setup_s}
        if win.switches:
            values["failover_s"] = (sum(s["failover_s"] for s in win.switches)
                                    / len(win.switches))
        for m in cell.end_to_end:
            if m["name"] not in values:
                raise RuntimeError(f"{cell.name} has no reading of "
                                   f"{m['name']}")
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        path = next(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
        if keep_trace:
            shutil.copyfile(path, keep_trace)
        red = xplane.reduce(path)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = {"cell": cell, "window": win, "trace": red,
               "peaks": peaks or counts.peaks(dev.device_kind),
               "counts": counts,
               "chips": len(devices), "seq_len": prog.seq_len}
        for m in cell.per_layer:
            v = metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if red.chips:
            device["busy_s"] = (sum(c.busy_s for c in red.chips)
                                / len(red.chips))
        device["window_s"] = red.window_s
        breakdown = _breakdown(red)

    ref, params0 = harness.reference(cell, seed, k_first, dev)
    nums = compare.numbers(live.readings, ref, params0)
    correct, rows = compare.judge(nums, cell.limits)
    correct = correct and win.failed == 0
    compared = {name: {"value": value, "limit": limit}
                for name, value, limit in rows}
    log(f"reference losses {ref.losses}")
    out = {"correct": bool(correct), "attempted": win.steps,
           "failed": win.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = compared
    for name, c in compared.items():
        log(f"{name} {c['value']!r} limit {c['limit']!r}")
    return out


def _breakdown(red) -> dict:
    ops: dict = {}
    for c in red.chips:
        for n, s in c.op_s.items():
            ops[n] = ops.get(n, 0.0) + s / len(red.chips)
    gaps = sorted((g for c in red.chips for g in c.gaps), reverse=True)
    return {"device_ops": [[n, s] for n, s in
                           sorted(ops.items(), key=lambda kv: -kv[1])[:10]],
            "idle_gaps": [[label, s] for s, label in gaps[:10]]}


if __name__ == "__main__":
    sys.exit(main())

"""Readings that the limits of a cell's check are set from, in one process.

    python bench/calibrate.py --workload <name> --seeds 12 --control 3 \
        --faults half_batch,no_exchange --fault-seeds 3 --out <file.jsonl>

For each seed: the program's first steps exactly as `bench/run.py` runs
them (no window), then the plain reference over the same steps, and the
compared numbers. On the first `--control` seeds, also the control: the
reference itself in float8 (`fp8=True`) put in the program's place. On the
first `--fault-seeds` seeds, also the program with each fault of
`bench/faults.py` planted. One JSON line per reading goes to `--out` and
to stdout. Needs a TPU, like `run.py`.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    import jax
    from bench import compare, faults, harness, workload
    cell = harness.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"needs {cell.chips} TPU chips; JAX found {devices}",
              file=sys.stderr)
        return 2
    devices = devices[:cell.chips]
    fault_names = [f for f in args.faults.split(",") if f]
    out = open(args.out, "a")

    def emit(**row):
        row.update(workload=cell.name)
        line = json.dumps(row)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    def program_readings(seed, fault=None):
        sched = workload.Schedule(cell.mix, seed)
        if fault is None:
            prog = harness.Program(cell, devices)
            live = harness.first_steps(prog, sched, seed)
        else:
            with faults.planted(fault) as wrap:
                prog = harness.Program(cell, devices, wrap_model=wrap)
                live = harness.first_steps(prog, sched, seed)
        harness.free(live.state)
        return live.readings, live.next_step

    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.perf_counter()
        prog, k = program_readings(seed)
        t1 = time.perf_counter()
        ref, params0 = harness.reference(cell, seed, k, devices[0])
        t2 = time.perf_counter()
        emit(seed=seed, kind="program",
             numbers=compare.numbers(prog, ref, params0),
             losses=prog.losses, ref_losses=ref.losses,
             program_s=t1 - t0, reference_s=t2 - t1)
        del prog
        if i < args.control:
            ctl, _ = harness.reference(cell, seed, k, devices[0], fp8=True)
            emit(seed=seed, kind="control",
                 numbers=compare.numbers(ctl, ref, params0),
                 losses=ctl.losses)
            del ctl
        if i < args.fault_seeds:
            for f in fault_names:
                got, _ = program_readings(seed, f)
                emit(seed=seed, kind=f"fault:{f}",
                     numbers=compare.numbers(got, ref, params0),
                     losses=got.losses)
                del got
        del ref, params0
    out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived[,note]`` CSV. Derived is the paper's
metric (completion time / fault-free T0 unless noted). The SimAI stand-in
is core.simulator (deterministic bandwidth-bound flow model).
"""
from __future__ import annotations

import sys
import time

from benchmarks import (anchors, appf_large_message, fig8_single_straggler,
                        fig9_multi_straggler, fig10_multi_gpu,
                        kernels_micro, schedule_gen_speed, sweep_summary,
                        table1_bounds)
from benchmarks.common import emit

# `kernels` runs last: it is the only module that starts JAX, and
# `sweep` forks a process pool, which must not come from a process that
# holds a JAX backend (on a TPU host, the chip).
MODULES = [
    ("fig8", fig8_single_straggler),
    ("fig9", fig9_multi_straggler),
    ("fig10", fig10_multi_gpu),
    ("table1", table1_bounds),
    ("schedgen", schedule_gen_speed),
    ("appF", appf_large_message),
    ("anchors", anchors),
    ("sweep", sweep_summary),
    ("kernels", kernels_micro),
]


def main() -> None:
    only = sys.argv[1] if len(sys.argv) > 1 else None
    print("name,us_per_call,derived,note")
    for name, mod in MODULES:
        if only and only not in name:
            continue
        t0 = time.time()
        rows = mod.run()
        emit(rows)
        print(f"# {name}: {len(rows)} rows in {time.time() - t0:.1f}s",
              file=sys.stderr)


if __name__ == '__main__':
    main()

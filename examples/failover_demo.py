"""Failover equivalence on a real 8-member DP ring (virtual devices).

Spawns a subprocess with 8 forced host (CPU) devices and trains the same model
twice: once healthy (native psum gradient sync) and once with member 3
degraded to 4/7 bandwidth (OptCC sync). The parameter trajectories must
match to fp tolerance - the paper's algorithm changes WHERE bytes flow,
never WHAT is computed.

    PYTHONPATH=src python examples/failover_demo.py

With ``--trace PATH`` the demo instead simulates the same degraded
scenario's OptCC schedule with telemetry, writes a Chrome trace (open in
chrome://tracing or Perfetto) and prints the critical-path stage breakdown
- no JAX subprocess is run. Add ``--algo NAME`` to force any algorithm
registered in `repro.core.registry` (ring, optcc, dbtree, torus2d, ...)
instead of letting the planner choose.

With ``--timeline [TRACE.json]`` the demo replays the degraded scenario
under a time-varying failure timeline (default: member 3 recovers at
0.35 T0; or any `ci/traces/*.json` file) and prints the static (no-replan)
vs mid-flight-replanned makespans next to the timeline lower bound - the
quantified payoff of re-planning when the fault pattern changes mid-
collective. Also JAX-free.
"""
import argparse
import os
import pathlib
import subprocess
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                       / "src"))

REPO = pathlib.Path(__file__).resolve().parent.parent

# The child is a CPU demo: it pins JAX to the host's 8 virtual devices, so
# on a TPU machine it neither claims nor waits for the chip.
CHILD = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.configs import get_config
from repro.models import build_model
from repro.optim import AdamWConfig
from repro.optim.schedules import constant
from repro.train import init_train_state, make_dp_failover_step
from repro.comms.fault import FaultState
from repro.data import DataConfig, SyntheticLM

cfg = get_config("qwen3-1.7b", smoke=True)
model = build_model(cfg)
opt = AdamWConfig(weight_decay=0.0)
mesh = Mesh(np.array(jax.devices()), ("data",))
data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=64,
                              global_batch=8))
fault = FaultState(axis_size=8, straggler=3, ell=1.75)
plan = fault.plan(n_elements=1_000_000)
print(f"degraded member 3 (l=1.75): planner chose {plan.algo}, "
      f"predicted overhead {plan.predicted_overhead:.3f}x vs healthy")

steps = {
    "healthy": make_dp_failover_step(model, mesh, opt, constant(1e-3),
                                     FaultState(axis_size=8)),
    "degraded": make_dp_failover_step(model, mesh, opt, constant(1e-3),
                                      fault),
}
states = {k: init_train_state(model, opt, seed=11, mesh=mesh)
          for k in steps}
for i in range(5):
    b = jax.tree.map(jnp.asarray, data.batch(i))
    line = f"step {i}:"
    for k in steps:
        states[k], m = steps[k](states[k], b)
        line += f"  {k} loss={float(m['loss']):.5f}"
    print(line)
diff = max(jax.tree.leaves(jax.tree.map(
    lambda a, b: float(jnp.max(jnp.abs(a - b))),
    states["healthy"].params, states["degraded"].params)))
print(f"max param divergence after 5 steps: {diff:.2e}")
assert diff < 1e-5
print("OK: OptCC-synced training is numerically identical to psum")
"""


def trace_scenario(path: str, algo: str = "auto") -> None:
    """Simulate the demo's degraded scenario (p=8, member 3 at l=1.75) with
    telemetry and write a Chrome trace plus a stage breakdown to stdout."""
    from repro import obs
    from repro.core.model import BandwidthProfile
    from repro.core.planner import make_plan
    from repro.core.simulator import simulate

    profile = BandwidthProfile.single_straggler(8, 1.75, straggler=3)
    plan = make_plan(profile, n=1_000_000, k=16, materialize="arrays",
                     algo=algo)
    res = simulate(plan.schedule, telemetry=True)
    obs.write_chrome_trace(res.telemetry, path, name="failover_demo")
    print(f"wrote {path}: algo={plan.algo} topology={plan.topology} "
          f"T={res.makespan:.6g} "
          f"(T0={plan.t0:.6g}, overhead {res.makespan / plan.t0:.3f}x, "
          f"{res.telemetry.nflows} flows)")
    for stage, v in sorted(obs.stage_breakdown(res.telemetry).items(),
                           key=lambda kv: -kv[1]):
        print(f"  {stage:10s} {v:14.3f}  ({v / res.makespan:6.1%})")


def timeline_scenario(trace_path: str | None) -> None:
    """Replay the demo's degraded scenario under a failure timeline and
    print no-replan vs replanned makespans next to the lower bound."""
    from repro.core import lower_bounds as lb
    from repro.core.model import BandwidthProfile, FaultTimeline
    from repro.core.planner import replay

    p, n = 8, 1_000_000
    profile = BandwidthProfile.single_straggler(p, 1.75, straggler=3)
    scale = lb.t0_fault_free(p, n, 1)
    if trace_path is None:
        name = "built-in recovery (member 3 heals at 0.35 T0)"
        events = [(0.0, 3, 1.75), (0.35 * scale, 3, 1.0)]
    else:
        from repro.sweeps.scenarios import load_trace
        tr = load_trace(trace_path)
        name = tr["name"]
        # Trace event times are in units of T0 (scale-free); ranks wrap.
        events = [(t * scale, int(r) % p, ell) for t, r, ell in tr["events"]]
    tl = FaultTimeline.make(events)
    rr = replay(profile, n, tl, k=16)
    print(f"timeline: {name} ({len(events)} events, p={p}, n={n})")
    print(f"  fault-free optimum T0     {rr.t0:14.1f}")
    print(f"  timeline lower bound      {rr.lower_bound:14.1f}  "
          f"({rr.lower_bound / rr.t0:.3f}x T0)")
    print(f"  static plan, no replan    {rr.t_noreplan:14.1f}  "
          f"({rr.t_noreplan / rr.t0:.3f}x T0)")
    print(f"  mid-flight replanned      {rr.t_replan:14.1f}  "
          f"({rr.t_replan / rr.t0:.3f}x T0, {rr.replans} replans)")
    if rr.adopted_replan:
        print(f"  re-planning saved {rr.t_noreplan - rr.t_replan:.1f} "
              f"({1 - rr.t_replan / rr.t_noreplan:.1%} of the no-replan "
              f"makespan)")
    else:
        print("  re-planning could not beat riding the original schedule")

    # The same timeline through an *imperfect* detector: probes lag,
    # quantize and occasionally lie, and a debounced controller decides
    # when an estimate is worth a re-plan.
    from repro.detect import ControllerConfig, DetectorConfig
    det = DetectorConfig.default(scale=scale)
    rr_det = replay(profile, n, tl, k=16, detector=det,
                    controller=ControllerConfig(policy="debounce"))
    d = rr_det.detection
    print(f"\nimperfect detector (probe every {det.probe_interval:.0f}, "
          f"latency {det.latency:.0f}, noise {det.noise:g}, "
          f"quant {det.quant:g}, fp {det.fp_rate:g}, fn {det.fn_rate:g}; "
          f"debounced x3):")
    true_rows = [f"t={t:9.1f} r{rank} l={ell:g}"
                 for t, rank, ell in sorted(
                     (float(t), r, v) for r, ch in
                     tl.changes(profile).items() for t, v in ch)]
    est_rows = [f"t={ev.t:9.1f} r{ev.rank} l={ev.ell:g}"
                for ev in d.timeline.events]
    width = max([24] + [len(s) for s in true_rows])
    print(f"  {'true profile changes':{width}s} | detector estimate")
    for i in range(max(len(true_rows), len(est_rows))):
        left = true_rows[i] if i < len(true_rows) else ""
        right = est_rows[i] if i < len(est_rows) else ""
        print(f"  {left:{width}s} | {right}")
    lag = (f"{rr_det.detect_lag_mean:.1f}"
           if rr_det.detect_lag_mean is not None else "-")
    print(f"  detected makespan         {rr_det.t_replan:14.1f}  "
          f"({rr_det.t_replan / rr.t_replan:.3f}x the zero-delay oracle; "
          f"{rr_det.replans} replans, {rr_det.false_replans} false, "
          f"{rr_det.suppressed} suppressed, mean lag {lag})")

    # Smoke check: on a trace that is *nothing but* false positives the
    # debounced controller must hold its fire - a re-plan here means the
    # debounce policy regressed, so the demo fails loudly.
    fp_det = DetectorConfig(probe_interval=0.04 * scale,
                            latency=0.01 * scale, fp_rate=0.25, seed=7)
    rr_fp = replay(profile, n, FaultTimeline.make([]), k=16,
                   detector=fp_det,
                   controller=ControllerConfig(policy="debounce"))
    print(f"  pure-FP trace (fp=0.25): debounced controller made "
          f"{rr_fp.replans} replans, suppressed {rr_fp.suppressed} blips")
    if rr_fp.replans:
        print("FAIL: debounce re-planned on a pure false-positive trace",
              file=sys.stderr)
        sys.exit(1)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="write a Chrome trace of the degraded scenario's "
                         "simulated schedule and exit (skips the JAX run)")
    ap.add_argument("--algo", default="auto",
                    help="schedule algorithm for --trace: 'auto' (planner "
                         "picks) or any name in repro.core.registry, e.g. "
                         "ring, optcc, dbtree, torus2d (default: auto)")
    ap.add_argument("--timeline", metavar="TRACE.json", nargs="?",
                    const="", default=None,
                    help="replay the degraded scenario under a failure "
                         "timeline (default: a mid-flight recovery; or a "
                         "ci/traces/*.json file) and print static vs "
                         "replanned makespans (skips the JAX run)")
    args = ap.parse_args()
    if args.timeline is not None:
        timeline_scenario(args.timeline or None)
        return
    if args.trace:
        trace_scenario(args.trace, algo=args.algo)
        return
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", CHILD], env=env,
                          text=True)
    sys.exit(proc.returncode)


if __name__ == "__main__":
    main()

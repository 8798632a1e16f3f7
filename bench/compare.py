"""The comparison that decides `correct`: the program's first steps against
the plain reference's, number by number, each against its limit.

Readings of one side (`Readings`): the loss of each of the first K steps,
the first gradient as the optimizer got it (after the sync and the clip),
and the parameters after the K steps. Leaves are compared one layer at a
time: a stacked `blocks/<leaf>` counts as one leaf per layer.

Numbers, each a share of the reference:
* `loss`: the largest |program - reference| / |reference| over the steps;
* `grad_norm`: over the leaves, the largest gap between the two norms of
  the first gradient, over the reference's norm of that leaf or of the
  median leaf, whichever is larger;
* `grad_diff`: the same, of the norm of the two gradients' difference;
* `update_norm`: as `grad_norm`, of the change of the parameters over
  the K steps, leaving out leaves whose reference gradient is under a
  thousandth of the median leaf's (Adam moves those by round-off alone);
* `update_diff`: as `grad_diff`, of that change.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

NAMES = ("loss", "grad_norm", "grad_diff", "update_norm", "update_diff")
QUIET_GRAD = 1e-3      # a leaf whose gradient is under this share of the
                       # median leaf's moves under Adam by round-off alone


@dataclasses.dataclass
class Readings:
    losses: list          # float per step
    grad: dict            # name -> float32 array, the first gradient
    params: dict          # name -> array, the parameters after K steps
    grad_scale: float = 1.0   # what `grad` is multiplied by to be it


def layer_leaves(tree: dict, prefix: str = "") -> dict:
    """{name: array} with stacked `blocks/*` leaves split per layer."""
    out = {}
    for k, v in tree.items():
        name = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(layer_leaves(v, name + "/"))
        elif name.startswith("blocks/"):
            for i in range(v.shape[0]):
                out[f"{name}/{i}"] = v[i]
        else:
            out[name] = v
    return out


@jax.jit
def _stats(p, r, p_scale=1.0):
    """(|p|, |r|, |p - r|) in float32 on the device, p times p_scale."""
    p, r = p.astype(jnp.float32) * p_scale, r.astype(jnp.float32)
    return jnp.stack([jnp.linalg.norm(p), jnp.linalg.norm(r),
                      jnp.linalg.norm(p - r)])


@jax.jit
def _delta_stats(p, r, p0):
    """_stats of the two changes p - p0 and r - p0."""
    p0 = p0.astype(jnp.float32)
    return _stats(p.astype(jnp.float32) - p0, r.astype(jnp.float32) - p0)


def _worst(stats: dict, keep) -> tuple[float, float]:
    """(worst norm gap, worst difference) over the leaves `keep`, each over
    max(the reference's norm of the leaf, the median leaf's)."""
    med = float(np.median([stats[k][1] for k in keep]))
    gap = diff = 0.0
    for k in keep:
        p, r, d = stats[k]
        base = max(r, med, 1e-30)
        g, d = abs(p - r) / base, d / base
        # A NaN would lose every max(): read it as the worst gap there is.
        gap = max(gap, g if np.isfinite(g) else np.inf)
        diff = max(diff, d if np.isfinite(d) else np.inf)
    return gap, diff


def numbers(prog: Readings, ref: Readings, params0: dict,
            device=None) -> dict:
    """Every compared number of the program against the reference, worked
    out leaf by leaf on `device` (default: the first device)."""
    if len(prog.losses) != len(ref.losses):
        raise ValueError("the two sides ran different numbers of steps")
    dev = device or jax.devices()[0]

    def put(a):
        return jax.device_put(np.asarray(a), dev)

    gaps = [abs(p - r) / abs(r) for p, r in zip(prog.losses, ref.losses)]
    out = {"loss": max(g if np.isfinite(g) else np.inf for g in gaps)}
    g_ref, g_prog = layer_leaves(ref.grad), layer_leaves(prog.grad)
    scale = np.float32(prog.grad_scale / ref.grad_scale)
    g = {k: np.asarray(_stats(put(g_prog[k]), put(g_ref[k]), scale),
                       np.float64) for k in g_ref}
    out["grad_norm"], out["grad_diff"] = _worst(g, list(g_ref))
    med = float(np.median([v[1] for v in g.values()]))
    keep = [k for k, v in g.items() if v[1] >= QUIET_GRAD * med]
    p0 = layer_leaves(params0)
    p_ref, p_prog = layer_leaves(ref.params), layer_leaves(prog.params)
    u = {k: np.asarray(_delta_stats(put(p_prog[k]), put(p_ref[k]),
                                    put(p0[k])), np.float64) for k in keep}
    out["update_norm"], out["update_diff"] = _worst(u, keep)
    return {k: (float(v) if np.isfinite(v) else float("inf"))
            for k, v in out.items()}


def judge(nums: dict, limits: dict) -> tuple[bool, list]:
    """(correct, [(name, value, limit), ...]) for the numbers with a limit.
    A number without a limit is reported and not judged."""
    rows = []
    ok = True
    for name in NAMES:
        if name not in nums:
            continue
        limit = limits.get(name)
        rows.append((name, nums[name], limit))
        if limit is not None and not nums[name] <= limit:
            ok = False
    return ok, rows

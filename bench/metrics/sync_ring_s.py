"""sync_ring_s: device seconds per step in OptCC's healthy subring,
`grad_sync/S1` (reduce-scatter) and `grad_sync/S4` (all-gather), every
`hop<t>` included, the mean over the cell's chips (bench/scopes.py)."""
from bench import scopes


def read(ctx):
    per_step = scopes.per_step(ctx)
    return None if per_step is None else scopes.total(
        per_step, "grad_sync/S1", "grad_sync/S4")

"""optimizer_s: device seconds per step under the program's `optimizer`
scope (learning rate, clip, the AdamW update), the mean over the cell's
chips (bench/scopes.py)."""
from bench import scopes


def read(ctx):
    per_step = scopes.per_step(ctx)
    return None if per_step is None else scopes.total(per_step, "optimizer")

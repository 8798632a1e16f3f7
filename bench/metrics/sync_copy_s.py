"""sync_copy_s: device seconds per step in the sync's copies,
`grad_sync/flatten` (cast, concatenate, pad) and `grad_sync/unflatten`
(slice, cast back, divide), the mean over the cell's chips
(bench/scopes.py). Where XLA fuses the unflatten into the optimizer, that
part reads under `optimizer_s`."""
from bench import scopes


def read(ctx):
    per_step = scopes.per_step(ctx)
    return None if per_step is None else scopes.total(
        per_step, "grad_sync/flatten", "grad_sync/unflatten")

"""sync_link_s: device seconds per step in OptCC's stages over the
straggler's link, `grad_sync/S3` (upload and fold-in) and `grad_sync/S2`
(return), the mean over the cell's chips (bench/scopes.py)."""
from bench import scopes


def read(ctx):
    per_step = scopes.per_step(ctx)
    return None if per_step is None else scopes.total(
        per_step, "grad_sync/S3", "grad_sync/S2")

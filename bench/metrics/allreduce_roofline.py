"""allreduce_roofline: the least time of an AllReduce of the gradient in
the parameters' dtype (2n(p-1)/p elements sent by each chip, at the chip's
ICI send rate from peaks.json) over collective_s, in %."""
import jax.numpy as jnp

from bench import weights
from bench.metrics import collective_s


def read(ctx):
    per_step = collective_s.read(ctx)
    if per_step is None:
        return None
    conf = ctx["cell"].conf
    least = ctx["counts"].allreduce_least_bytes(
        weights.count(conf), ctx["chips"],
        jnp.dtype(conf["dtypes"]["params"]).itemsize)
    return 100.0 * least / ctx["peaks"]["ici_send_bytes_per_s"] / per_step

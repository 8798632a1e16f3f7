"""Names of the train step's stages inside the device program.

The DP failover step (`train/step.make_dp_failover_step`) and its
collectives (`comms/collectives.py`) wrap their work in `jax.named_scope`s
with these names. A scope is op metadata only: it lands in every HLO
instruction's `op_name` (`.../grad_sync/S1/hop0/ppermute`) and changes
nothing that runs. A profiler trace names instructions, so joining a trace
with the compiled program's text gives device time per stage.

Top-level scopes partition the step:

  model      the loss and its gradient; backward ops carry `transpose(`
             in their op_name, forward ops do not;
  grad_sync  from the per-member gradients to the averaged ones;
  optimizer  learning rate, clip and the AdamW update.

Inside `grad_sync` the degraded (OptCC) path uses the simulator's stage
vocabulary (`core.model.STAGE_NAMES`), so a device stage time sits beside
`repro.obs.stage_breakdown` for the same schedule:

  flatten    cast to float32, concatenate, pad;
  S3         the straggler's upload and the peer's fold-in;
  S1         the healthy subring's reduce-scatter, one `hop<t>` per round;
  S4         the healthy subring's all-gather, one `hop<t>` per round;
  S2         the return to the straggler and its select;
  unflatten  slice, cast back, divide by the DP width;
  loss       the loss's psum.

The healthy path has `psum` (and `loss`).
"""
from __future__ import annotations

from repro.core.model import STAGE_NAMES

MODEL = "model"
GRAD_SYNC = "grad_sync"
OPTIMIZER = "optimizer"
TOP = (MODEL, GRAD_SYNC, OPTIMIZER)

FLATTEN = "flatten"
UNFLATTEN = "unflatten"
LOSS = "loss"
PSUM = "psum"
S1, S2, S3, S4 = STAGE_NAMES[:4]
SYNC = (FLATTEN, S3, S1, S4, S2, UNFLATTEN, LOSS, PSUM)


def hop(t: int) -> str:
    """The scope of round `t` of a ring stage."""
    return f"hop{t}"

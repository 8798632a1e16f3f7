"""Plain references, one module per kind of model, named by a configuration's `reference` key.

A configuration enters the benchmark as files only: its JSON under
`bench/configs/`, its traffic and limits, and the module here that its
`reference` key names. The module holds everything that is particular to
its kind of model; the harness finds it through `module(conf)`:

* `shapes(conf)`: {name: shape} of every parameter leaf, `blocks/<leaf>`
  stacked over the layers: the tree that the program's step and the
  reference both read (`bench/weights.py` makes it from the seed).
* `leaf_rules(conf)`, optional: {name: {"dtype": ..., "init": ...}} for
  the leaves that differ from `bench/weights.py`'s rule (the parameters'
  dtype; "normal" for matrices, "zeros" for norm offsets).
* `flops_per_token(conf, seq_len)`: the model FLOPs of one training
  token, whose docstring states the formula (`step_mfu` reads it).
* `program_settings(conf)`: the program's `ModelConfig` fields, read from
  the file's published keys; the harness applies the file's optional
  `program` object over them.
* `loss_and_grad(conf, params, tokens, labels, fp8=False)` and
  `adamw_step(...)`: the reference itself.
"""
import importlib


def module(conf: dict):
    """The reference module that `conf["reference"]` names."""
    return importlib.import_module(f"{__name__}.{conf['reference']}")

"""CPU rehearsals of the benchmark: four virtual devices, toy widths.

Run on their own: `python -m pytest bench/tests` from the repository root.
"""
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=4")

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TOY = {"num_hidden_layers": 2, "hidden_size": 64, "intermediate_size": 128,
       "num_attention_heads": 4, "num_key_value_heads": 2,
       "vocab_size": 256}


def toy_conf(conf: dict, **over) -> dict:
    """A configuration file at toy widths: TOY, then the file's optional
    `toy` object (a MoE file's toy expert count, MHA's toy heads), then
    `over`."""
    return {**conf, **TOY, **conf.get("toy", {}), **over}


def toy(cell, seq_len: int = 64, **conf):
    """The cell at toy widths and a short sequence, everything else as
    its files say."""
    import dataclasses
    mix = dict(cell.mix, seq_len=seq_len)
    return dataclasses.replace(cell, conf=toy_conf(cell.conf, **conf),
                               mix=mix)


def config_file(config: str) -> dict:
    import json
    return json.loads((ROOT / "bench" / "configs" / f"{config}.json")
                      .read_text())


CONFIGS = sorted(p.stem for p in (ROOT / "bench" / "configs").glob("*.json"))
MIXES = sorted(p.stem for p in (ROOT / "bench" / "traffic").glob("*.json"))
READERS = sorted(p.stem for p in (ROOT / "bench" / "metrics").glob("*.py"))


def mix_cell(mix: str, config: str = "minicpm-2b"):
    """A cell of `config` under traffic `mix`, at toy widths, with the
    first BENCHMARK.json cell's limits, every end-to-end metric the mix can
    have and every per-layer reader: every mix file and reader is
    rehearsed, also those no committed cell uses yet."""
    import json
    from bench import harness
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    first = harness.load_cell(spec["workloads"][0]["name"])
    conf = config_file(config)
    m = json.loads((ROOT / "bench" / "traffic" / f"{mix}.json").read_text())
    e2e = [{"name": "tokens_per_s", "unit": "tokens/s"},
           {"name": "setup_s", "unit": "s"}]
    if m["faults"]["kind"] == "trace":
        e2e.append({"name": "failover_s", "unit": "s"})
    cell = harness.Cell(f"{config}.{mix}", m["dp"], config, conf, mix, m,
                        first.limits, e2e,
                        [{"name": r, "unit": "-"} for r in READERS])
    return toy(cell)

"""What a configuration brings through its files: the parameter layout,
the FLOP count and the program's settings, looked up through its
`reference` module and its `program` and `toy` objects.

The guard: for every configuration at toy widths, and for a MoE layout
injected as a reference module of the test's own, the tree that
`bench/weights.py` makes is the tree the program's model initialises.
The pins: today's configurations read what they read before the layout,
the count and the settings moved into their reference module.
"""
import hashlib
import sys
import types

import numpy as np
import pytest

import jax

from conftest import CONFIGS, config_file, toy_conf
from bench import counts, harness, weights
from bench.refs import dense_decoder

MOE = "injected-moe"


def _moe_module():
    """A reference module for a MoE decoder: the dense decoder's layout
    with each layer's SwiGLU replaced by a float32 router and E experts,
    as the program's `models/moe.py` holds them; its FLOP count takes
    the router and top_k of the experts as active."""
    mod = types.ModuleType("bench.refs.injected_moe")

    def settings(conf):
        return {**dense_decoder.program_settings(conf), **conf["program"]}

    def shapes(conf):
        s = settings(conf)
        L, d, f, E = s["n_layers"], s["d_model"], s["d_ff"], s["n_experts"]
        out = {k: v for k, v in dense_decoder.shapes(conf).items()
               if k not in ("blocks/w_gate", "blocks/w_up", "blocks/w_down")}
        out.update({"blocks/router": (L, d, E),
                    "blocks/e_gate": (L, E, d, f),
                    "blocks/e_up": (L, E, d, f),
                    "blocks/e_down": (L, E, f, d)})
        return out

    def flops_per_token(conf, seq_len):
        s = settings(conf)
        L, d, f = s["n_layers"], s["d_model"], s["d_ff"]
        dense_ffn = 6 * L * 3 * d * f
        active = 6 * L * (d * s["n_experts"] + s["top_k"] * 3 * d * f)
        return dense_decoder.flops_per_token(conf, seq_len) - dense_ffn + active

    mod.shapes = shapes
    mod.leaf_rules = lambda conf: {"blocks/router": {"dtype": "float32"}}
    mod.flops_per_token = flops_per_token
    mod.program_settings = dense_decoder.program_settings
    return mod


def _moe_file() -> dict:
    """A MoE configuration file as a later configuration would bring it:
    published expert counts under `program`, toy ones under `toy`."""
    conf = dict(config_file("internlm2-1.8b"), reference="injected_moe")
    conf["program"] = {"family": "moe", "n_experts": 64, "top_k": 6}
    conf["toy"] = {"program": {"family": "moe", "n_experts": 4, "top_k": 2}}
    return conf


@pytest.fixture
def injected_moe(monkeypatch):
    monkeypatch.setitem(sys.modules, "bench.refs.injected_moe",
                        _moe_module())


def _leaves(tree):
    return [(jax.tree_util.keystr(p), leaf.shape, np.dtype(leaf.dtype))
            for p, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]]


@pytest.mark.parametrize("config", CONFIGS + [MOE])
def test_weight_tree_is_the_programs(config, request):
    """Same structure, shapes and dtypes as the program's own init."""
    from repro.models import build_model
    if config == MOE:
        request.getfixturevalue("injected_moe")
        conf = toy_conf(_moe_file())
    else:
        conf = toy_conf(config_file(config))
    cfg = harness.model_config(conf, config)
    key = jax.random.PRNGKey(0)
    want = jax.eval_shape(build_model(cfg).init, key)
    got = jax.eval_shape(lambda: weights._make(conf, key))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert _leaves(got) == _leaves(want)
    assert weights.count(conf) == sum(int(np.prod(s)) for _, s, _ in
                                      _leaves(want))
    if config == MOE:
        assert (cfg.family, cfg.n_experts, cfg.top_k) == ("moe", 4, 2)
        assert got["blocks"]["router"].dtype == np.float32
        assert "w_gate" not in got["blocks"]
        mod = sys.modules["bench.refs.injected_moe"]
        assert (counts.flops_per_token(conf, 64)
                == mod.flops_per_token(conf, 64)
                != dense_decoder.flops_per_token(conf, 64))


def test_unknown_program_field_is_an_error():
    conf = dict(config_file("internlm2-1.8b"),
                program={"family": "moe", "n_expert": 4})
    with pytest.raises(ValueError, match="n_expert"):
        harness.model_config(conf, "internlm2-1.8b")


def test_leaf_rule_for_no_leaf_is_an_error(injected_moe, monkeypatch):
    mod = sys.modules["bench.refs.injected_moe"]
    monkeypatch.setattr(mod, "leaf_rules",
                        lambda conf: {"blocks/w_gate": {"dtype": "float32"}})
    with pytest.raises(ValueError, match="blocks/w_gate"):
        weights.rules(toy_conf(_moe_file()))


# ----------------------------------------------------------------------------
# today's readings, taken before the layout, the FLOP count and the settings
# moved into the reference module
# ----------------------------------------------------------------------------

PINS = {
    "internlm2-1.8b": dict(
        flops=3_704_094_720, matmul=567_017_472, count=756_574_208,
        digest="0f1155c5367c5beb8eb994194f74608ab3bb962bdae3b126127a1fa567dd7fb7",
        cfg=dict(n_layers=6, d_model=2048, n_heads=16, n_kv_heads=8,
                 d_ff=8192, vocab_size=92544, head_dim=128,
                 rope_theta=1000000.0, tie_embeddings=False)),
    "minicpm-2b": dict(
        flops=3_388_552_704, matmul=527_010_048, count=527_030_784,
        digest="341edba710cb32c8e9b75159df82f585382404abf4cdde4e7509f8b94208deb4",
        cfg=dict(n_layers=4, d_model=2304, n_heads=36, n_kv_heads=36,
                 d_ff=5760, vocab_size=122753, head_dim=64,
                 rope_theta=10000.0, tie_embeddings=True)),
}


def _digest(params) -> str:
    h = hashlib.sha256()
    for path, leaf in sorted(jax.tree_util.tree_flatten_with_path(params)[0],
                             key=lambda kv: jax.tree_util.keystr(kv[0])):
        a = np.asarray(leaf)
        for part in (jax.tree_util.keystr(path), str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("config", sorted(PINS))
def test_todays_readings_are_pinned(config):
    from repro.configs.base import ModelConfig
    pin, conf = PINS[config], config_file(config)
    assert conf["reference"] == "dense_decoder"
    assert counts.flops_per_token(conf, 2048) == pin["flops"]
    assert dense_decoder.matmul_params(conf) == pin["matmul"]
    assert weights.count(conf) == pin["count"]
    assert harness.model_config(conf, config) == ModelConfig(
        name=config, family="dense", norm_eps=1e-05, param_dtype="bfloat16",
        compute_dtype="bfloat16", moment_dtype="float32", **pin["cfg"])
    params = weights._make(toy_conf(conf), jax.random.PRNGKey(0))
    assert _digest(params) == pin["digest"]

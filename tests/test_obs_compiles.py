"""The process-wide compile counter (repro.obs.compiles) on CPU."""
import time

import pytest

import jax
import jax.numpy as jnp
from jax._src import monitoring   # the listener lists are not public
from jax.experimental.compilation_cache import compilation_cache

from repro.obs import compiles


@pytest.fixture
def disk_cache(tmp_path):
    """A persistent compile cache of this test's own, every program kept."""
    keys = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compilation_cache.reset_cache()
    yield tmp_path
    for k, v in before.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def _program(scale: float):
    def f(x):
        y = jax.jit(lambda a: jnp.sin(a) * scale)(x)     # a nested trace
        return jnp.cumsum(y) + scale
    return jax.jit(f)


def test_counts_a_fresh_compile_then_a_cache_load(disk_cache):
    compiles.install()
    x = jnp.arange(64, dtype=jnp.float32)
    before = compiles.snapshot()
    t0 = time.perf_counter()
    _program(3.25)(x).block_until_ready()
    wall = time.perf_counter() - t0
    got = compiles.since(before)
    assert got["compiles"] == 1 and got["cache_loads"] == 0
    assert got["trace_s"] > 0 and got["lower_s"] > 0 and got["compile_s"] > 0
    # the nested jit's trace lies inside its parent's: counted once
    assert got["total_s"] <= wall
    assert got["total_s"] == pytest.approx(
        got["trace_s"] + got["lower_s"] + got["compile_s"])

    jax.clear_caches()                   # only the disk cache remains
    before = compiles.snapshot()
    _program(3.25)(x).block_until_ready()
    got = compiles.since(before)
    assert got["compiles"] == 1 and got["cache_loads"] == 1
    assert 0 < got["cache_load_s"] <= got["compile_s"]


def test_installing_twice_counts_once():
    compiles.install()
    compiles.install()
    assert monitoring.get_event_time_span_listeners().count(
        compiles._on_span) == 1
    assert monitoring.get_event_duration_listeners().count(
        compiles._on_duration) == 1
    x = jnp.ones(8)
    x.block_until_ready()
    before = compiles.snapshot()
    _program(7.5)(x).block_until_ready()
    assert compiles.since(before)["compiles"] == 1


def test_a_nested_span_is_counted_once():
    compiles.install()
    before = compiles.snapshot()
    compiles._on_span(compiles.TRACE, 100.0, 100.5)      # inner, reported first
    compiles._on_span(compiles.TRACE, 100.6, 100.8)
    compiles._on_span(compiles.TRACE, 99.0, 101.0)       # their parent
    compiles._on_span("/some/other/event", 0.0, 5.0)
    assert compiles.since(before)["trace_s"] == pytest.approx(2.0)


def test_enable_compile_cache_starts_the_counter(monkeypatch, tmp_path):
    from repro.launch import cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    cache.enable_compile_cache()
    assert compiles._on_span in monitoring.get_event_time_span_listeners()

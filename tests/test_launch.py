"""Entry-point plumbing for the chip: where the compilation cache goes,
the published peaks table, and the dense-snapshot footprint guard."""
import os

import jax
import numpy as np
import pytest

from repro.core import QuakeConfig, QuakeIndex
from repro.core import multiquery as mq
from repro.launch import compile_cache
from repro.roofline import analysis


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_cache_dir_from_environment_is_left_alone(monkeypatch,
                                                  restore_cache_dir):
    monkeypatch.setenv(compile_cache.CACHE_ENV, "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.configure_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_dir_defaults_to_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    got = compile_cache.configure_compile_cache()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert got == os.path.join(repo, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got


def test_peaks_table_keyed_by_device_kind():
    v5e = analysis.peaks("TPU v5 lite")
    assert v5e["peak_flops"] == 197e12 and v5e["hbm_bw"] == 819e9
    assert v5e["peak_int8_ops"] == 393e12
    with pytest.raises(ValueError, match="no published peaks"):
        analysis.peaks("cpu")


def _index():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(2000, 8)).astype(np.float32)
    return QuakeIndex.build(x, num_partitions=8, kmeans_iters=2,
                            config=QuakeConfig())


def test_executor_footprint_reports_padding():
    ex = mq.BatchedSearchExecutor(_index())
    assert ex.footprint() == {}
    snap = ex.snapshot()
    fp = ex.footprint()
    p, cap, d = snap.data.shape
    assert (fp["partitions"], fp["capacity"], fp["dim"]) == (p, cap, d)
    assert fp["device_bytes"] == p * cap * d * 4
    assert fp["live_vectors"] == 2000
    assert fp["live_bytes"] == 2000 * d * 4 <= fp["device_bytes"]
    assert fp["full_rebuilds"] == 1 and fp["delta_refreshes"] == 0


@pytest.mark.parametrize("limit,fits", [(None, True), (10 ** 12, True),
                                        (1000, False)])
def test_snapshot_larger_than_device_is_refused(monkeypatch, limit, fits):
    """The executor refuses, before staging it, a dense snapshot larger
    than the device reports it can hold."""
    class Dev:
        def memory_stats(self):
            return None if limit is None else {"bytes_limit": limit}
    monkeypatch.setattr(mq.jax, "devices", lambda: [Dev()])
    ex = mq.BatchedSearchExecutor(_index())
    if fits:
        ex.refresh()
    else:
        with pytest.raises(MemoryError, match="largest partition"):
            ex.refresh()
        assert ex.footprint() == {}

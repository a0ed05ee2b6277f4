"""Compile the main-path Pallas kernels for a described TPU v5e.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
unaligned block shapes, unsupported primitives, layouts it cannot infer.
These tests compile each kernel with ``interpret=False`` for one chip of a
described ``v5e:2x2`` topology, at the widths the served path runs (d=768,
k=10 and k=100), and check that the program holds the Mosaic kernel.
Nothing runs, so results and times are out of scope here.

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU library, and test workers
import every test file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.kmeans_assign import kmeans_assign_pallas
from repro.kernels.scan_topk import scan_topk_pallas
from repro.kernels.scan_topk_indexed import (scan_topk_indexed_pallas,
                                             scan_topk_indexed_q8_pallas)

D = 768          # served width (the smoke's Wikipedia-style corpus)
B = 64           # one serving flush
P, S, U = 64, 2048, 32
K_PAD = {10: 16, 100: 128}   # ops' k_pad for k=10 and k=100


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache, so keep it out of the cache altogether."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("k", sorted(K_PAD))
def test_scan_topk_compiles(k, one_chip, no_compile_cache):
    fn = functools.partial(scan_topk_pallas, k_pad=K_PAD[k], metric="ip",
                           block_q=B, block_s=512, interpret=False)
    txt = _compile(fn, one_chip, ((B, D), jnp.float32),
                   ((4096, D), jnp.float32), ((1, 4096), jnp.float32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("k", sorted(K_PAD))
def test_scan_topk_indexed_compiles(k, dtype, one_chip, no_compile_cache):
    fn = functools.partial(scan_topk_indexed_pallas, k_pad=K_PAD[k],
                           metric="ip", block_q=B, block_s=512,
                           interpret=False)
    txt = _compile(fn, one_chip, ((B, D), dtype), ((P, S, D), dtype),
                   ((P, S), jnp.float32), ((U,), jnp.int32),
                   ((B, U), jnp.float32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("k", sorted(K_PAD))
def test_scan_topk_indexed_q8_compiles(k, one_chip, no_compile_cache):
    fn = functools.partial(scan_topk_indexed_q8_pallas, k_pad=K_PAD[k],
                           metric="ip", block_q=B, block_s=512,
                           interpret=False)
    txt = _compile(fn, one_chip, ((B, D), jnp.int8), ((B, 1), jnp.float32),
                   ((P, S, D), jnp.int8), ((P, S), jnp.float32),
                   ((P, S), jnp.float32), ((B, U), jnp.float32),
                   ((U,), jnp.int32), ((B, U), jnp.float32))
    assert "tpu_custom_call" in txt


def test_kmeans_assign_compiles(one_chip, no_compile_cache):
    fn = functools.partial(kmeans_assign_pallas, block_n=512, block_c=128,
                           interpret=False)
    txt = _compile(fn, one_chip, ((4096, D), jnp.float32),
                   ((1024, D), jnp.float32), ((1, 1024), jnp.float32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("u_pad", [8, 256])
def test_sharded_round_compiles(u_pad, topo, no_compile_cache, monkeypatch):
    """The served round over a snapshot sharded on a described four-chip
    mesh (``ServingConfig.part_shards=4``): one program holding the
    Mosaic scan kernel and the all-gather that merges the shards."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS
    from repro.core import QuakeIndex
    from repro.core.snapshot import PartitionLayout
    from repro.core.multiquery import BatchedSearchExecutor
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)   # Mosaic, not interpret
    x = np.random.default_rng(0).normal(size=(64, 8)).astype(np.float32)
    lay = PartitionLayout(Mesh(np.array(topo.devices[:4]), ("data",)))
    ex = BatchedSearchExecutor(QuakeIndex.build(x, num_partitions=4),
                               layout=lay)
    fn = ex._sharded_round(u_pad, 10, "pallas", False)
    p, m, s = 4 * 256, 512, 1024
    part, rep = (NamedSharding(lay.mesh, PS("data")),
                 NamedSharding(lay.mesh, PS()))
    args = [jax.ShapeDtypeStruct(sh, dt, sharding=sd) for sh, dt, sd in (
        ((16, D), jnp.float32, rep), ((16, m), jnp.int32, rep),
        ((16, m), jnp.bool_, rep), ((4,), jnp.int32, part),
        ((p, s, D), jnp.float32, part), ((p, s), jnp.bool_, part))]
    txt = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in txt and "all-gather" in txt

"""Pallas TPU kernel: fused distance + argmin (k-means assignment step).

Quake's maintenance path (split / refinement / insert routing, §4.2) is
dominated by nearest-centroid assignment.  The naive jnp form materializes the
(N, C) distance matrix in HBM; this kernel keeps only a running
(min-dist, argmin) pair per point in VMEM while streaming centroid blocks —
one HBM pass over points and centroids.

Grid = (point_tiles, centroid_blocks), dimension_semantics
(PARALLEL, ARBITRARY); scratch carries the running minimum across the
sequential centroid dimension.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_compat
from .ref import MASK_DIST
from .scan_topk import mxu_precision

Array = jax.Array


def _kmeans_assign_kernel(x_ref, c_ref, aux_ref, out_a_ref, out_d_ref,
                          run_d, run_a, *, nblocks: int, block_c: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        run_d[...] = jnp.full_like(run_d, MASK_DIST)
        run_a[...] = jnp.full_like(run_a, -1)

    x = x_ref[...]        # (TN, d)
    c = c_ref[...]        # (TC, d)
    aux = aux_ref[...]    # (1, TC): ||c||^2 + pad bias
    xc = jax.lax.dot_general(
        x, c, (((1,), (1,)), ((), ())),
        precision=mxu_precision(x.dtype),
        preferred_element_type=jnp.float32)
    dist = aux.astype(jnp.float32) - 2.0 * xc          # (TN, TC)

    base = j * block_c
    cidx = base + jax.lax.broadcasted_iota(jnp.int32, dist.shape, 1)

    blk_min = jnp.min(dist, axis=1, keepdims=True)      # (TN, 1)
    # argmin without gathers: smallest index attaining the min.
    is_min = dist <= blk_min
    blk_arg = jnp.min(jnp.where(is_min, cidx, jnp.int32(2**30)), axis=1,
                      keepdims=True)

    better = blk_min < run_d[...]
    run_d[...] = jnp.where(better, blk_min, run_d[...])
    run_a[...] = jnp.where(better, blk_arg, run_a[...])

    @pl.when(j == nblocks - 1)
    def _write():
        out_d_ref[...] = run_d[...]
        out_a_ref[...] = run_a[...]


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_c", "interpret"))
def kmeans_assign_pallas(xs: Array, centroids: Array, aux: Array, *,
                         block_n: int = 512, block_c: int = 128,
                         interpret: bool = True) -> Tuple[Array, Array]:
    """Fused assignment.  Pre-padded shapes:

    xs:        (N, d), N % block_n == 0
    centroids: (C, d), C % block_c == 0
    aux:       (1, C) = ||c||^2 (+ MASK_DIST bias on padded centroid rows)

    Returns (assign int32 (N, 1), min_dist (N, 1)); min_dist omits the
    per-point ||x||^2 term (caller adds it back if actual distances needed).
    """
    N, d = xs.shape
    C, _ = centroids.shape
    assert N % block_n == 0 and C % block_c == 0, (N, C)
    nn, nb = N // block_n, C // block_c

    kernel = functools.partial(_kmeans_assign_kernel, nblocks=nb,
                               block_c=block_c)
    out_a, out_d = pl.pallas_call(
        kernel,
        grid=(nn, nb),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_c, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, block_c), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, 1), jnp.int32),
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_n, 1), jnp.float32),
            pltpu.VMEM((block_n, 1), jnp.int32),
        ],
        compiler_params=pallas_compat.compiler_params(
            dimension_semantics=(pallas_compat.PARALLEL,
                                 pallas_compat.ARBITRARY)),
        interpret=interpret,
        name="quake_kmeans_assign",
    )(xs, centroids, aux)
    return out_a, out_d

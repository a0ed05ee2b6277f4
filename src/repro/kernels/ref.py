"""Pure-jnp oracles for the Pallas kernels.

These are the ground-truth implementations used by tests (``assert_allclose`` /
recall@k against the kernels) and as the default CPU execution path (the Pallas
kernels run in ``interpret=True`` mode on CPU, which is far too slow for
benchmarks; the jnp path is what XLA:CPU executes).

The paper's hot loop is the *partition scan*: distances from a query batch to a
block of database vectors plus top-k selection (Quake §6, SimSIMD/AVX512 on x86
→ MXU matmul on TPU).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array

# XLA dots on a TPU default to one bf16 pass, which the ||q||^2 + ||x||^2 -
# 2 q.x identity cannot afford (k-means at d=768 collapsed on the chip);
# the references and the f32 jnp paths ask for full f32 precision.
HIGHEST = jax.lax.Precision.HIGHEST

# Large-but-finite sentinel: keeps masked lanes inert without generating NaNs
# in downstream arithmetic (inf - inf).  Plain float so Pallas kernels can use
# it without capturing a traced constant.
MASK_DIST = 3.0e38


def pairwise_l2_sq(queries: Array, xs: Array) -> Array:
    """Squared L2 distances, (Q, d) x (N, d) -> (Q, N), via the matmul identity.

    ||q - x||^2 = ||q||^2 + ||x||^2 - 2 q.x  — one GEMM + rank-1 updates, the
    MXU-friendly form the Pallas kernel mirrors.
    """
    q2 = jnp.sum(queries * queries, axis=-1, keepdims=True)  # (Q, 1)
    x2 = jnp.sum(xs * xs, axis=-1)  # (N,)
    qx = jnp.matmul(queries, xs.T, precision=HIGHEST)  # (Q, N)
    d = q2 + x2[None, :] - 2.0 * qx
    return jnp.maximum(d, 0.0)


def pairwise_ip(queries: Array, xs: Array) -> Array:
    """Inner-product scores, (Q, d) x (N, d) -> (Q, N)."""
    return jnp.matmul(queries, xs.T, precision=HIGHEST)


def scan_distances(queries: Array, xs: Array, metric: str = "l2",
                   valid: Optional[Array] = None) -> Array:
    """Distance matrix in *minimization* convention.

    For ``metric="ip"`` we return negated scores so that smaller is always
    better; callers that need raw scores negate back.  ``valid`` is an (N,)
    bool mask; invalid rows get MASK_DIST.
    """
    if metric == "l2":
        d = pairwise_l2_sq(queries, xs)
    elif metric == "ip":
        d = -pairwise_ip(queries, xs)
    else:
        raise ValueError(f"unknown metric: {metric}")
    if valid is not None:
        d = jnp.where(valid[None, :], d, MASK_DIST)
    return d


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def scan_topk_ref(queries: Array, xs: Array, k: int, metric: str = "l2",
                  valid: Optional[Array] = None) -> Tuple[Array, Array]:
    """Oracle fused scan: top-k (distances, indices) per query.

    Returns distances in minimization convention (negated scores for ip) and
    int32 indices into ``xs``.  Padded/invalid entries surface as MASK_DIST
    with index -1.
    """
    d = scan_distances(queries, xs, metric, valid)
    neg = -d
    vals, idx = jax.lax.top_k(neg, k)  # top_k maximizes
    dists = -vals
    idx = jnp.where(dists >= MASK_DIST, -1, idx)
    return dists, idx.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=())
def kmeans_assign_ref(xs: Array, centroids: Array,
                      valid: Optional[Array] = None) -> Tuple[Array, Array]:
    """Oracle fused assign: nearest centroid (argmin L2) per point.

    Returns (assignments int32 (N,), min squared distances (N,)).  Invalid
    points (mask False) get assignment -1.
    """
    d = pairwise_l2_sq(xs, centroids)  # (N, C)
    assign = jnp.argmin(d, axis=-1).astype(jnp.int32)
    mind = jnp.min(d, axis=-1)
    if valid is not None:
        assign = jnp.where(valid, assign, -1)
        mind = jnp.where(valid, mind, MASK_DIST)
    return assign, mind


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def scan_selected_ref(queries: Array, data: Array, aux_valid: Array,
                      sel: Array, qmask: Array, k: int, metric: str = "l2",
                      ) -> Tuple[Array, Array]:
    """Oracle for the indexed scan: top-k over a union of selected blocks.

    queries (B, d); data (P, S, d); aux_valid (P, S) bool (True = real row);
    sel (U,) int32 partition ids; qmask (B, U) bool (True = query b wants
    block u).  Returns (dists (B, k) ascending, flat idx = partition*S+slot),
    minimization convention, misses = MASK_DIST / -1.
    """
    blocks = jnp.take(data, sel, axis=0).astype(jnp.float32)  # (U, S, d)
    valid = jnp.take(aux_valid, sel, axis=0)        # (U, S)
    queries = queries.astype(jnp.float32)
    if metric == "l2":
        x2 = jnp.sum(blocks * blocks, axis=-1)      # (U, S)
        qx = jnp.einsum("usd,bd->bus", blocks, queries, precision=HIGHEST)
        q2 = jnp.sum(queries * queries, axis=-1)[:, None, None]
        dist = jnp.maximum(x2[None] - 2.0 * qx + q2, 0.0)
    else:
        dist = -jnp.einsum("usd,bd->bus", blocks, queries,
                           precision=HIGHEST)
    dist = jnp.where(valid[None], dist, MASK_DIST)
    dist = jnp.where(qmask[:, :, None], dist, MASK_DIST)
    S = data.shape[1]
    flat_idx = (sel[:, None] * S
                + jnp.arange(S, dtype=jnp.int32)[None, :])  # (U, S)
    b = queries.shape[0]
    dist = dist.reshape(b, -1)
    idx = jnp.broadcast_to(flat_idx.reshape(1, -1), dist.shape)
    k_eff = min(k, dist.shape[1])
    vals, pos = jax.lax.top_k(-dist, k_eff)
    d_out, i_out = -vals, jnp.take_along_axis(idx, pos, axis=1)
    i_out = jnp.where(d_out >= MASK_DIST, -1, i_out)
    return d_out, i_out.astype(jnp.int32)


def merge_topk(dists_a: Array, idx_a: Array, dists_b: Array, idx_b: Array,
               k: int) -> Tuple[Array, Array]:
    """Merge two sorted-or-not top-k candidate sets per query row -> top-k."""
    d = jnp.concatenate([dists_a, dists_b], axis=-1)
    i = jnp.concatenate([idx_a, idx_b], axis=-1)
    vals, sel = jax.lax.top_k(-d, k)
    return -vals, jnp.take_along_axis(i, sel, axis=-1)

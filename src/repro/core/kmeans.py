"""Jit-compiled k-means for partition construction, split, and refinement.

Shapes are padded to power-of-2 buckets with a validity mask so the jit cache
stays bounded while partitions grow/shrink (the dynamic index calls this with
ever-changing sizes).  Empty clusters are reseeded to the points currently
farthest from their assigned centroid (standard Lloyd repair), keeping all k
clusters alive — Quake's maintenance assumes every partition has a centroid.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops
from ..kernels.ref import MASK_DIST, pairwise_l2_sq

Array = jax.Array


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


@functools.partial(jax.jit, static_argnames=("k", "iters"))
def _lloyd(xp: Array, mask: Array, init_c: Array, cmask: Array, k: int,
           iters: int) -> Tuple[Array, Array, Array]:
    """Masked Lloyd iterations.  xp (Np, d) padded points, mask (Np,) bool,
    init_c (k, d), cmask (k,) bool marks the real centroids (padding rows
    never win a point and are never reseeded).  Returns (centroids,
    assign, objective)."""

    def dists(c):
        d = pairwise_l2_sq(xp, c)                      # (Np, k)
        d = jnp.where(cmask[None, :], d, MASK_DIST)
        return jnp.where(mask[:, None], d, MASK_DIST)

    def step(c, _):
        d = dists(c)
        assign = jnp.argmin(d, axis=1)
        mind = jnp.min(d, axis=1)
        w = mask.astype(xp.dtype)
        sums = jax.ops.segment_sum(xp * w[:, None], assign, num_segments=k)
        cnts = jax.ops.segment_sum(w, assign, num_segments=k)
        new_c = jnp.where(cnts[:, None] > 0,
                          sums / jnp.maximum(cnts[:, None], 1.0), c)
        # Reseed empties to the currently worst-fit points (masked-valid).
        worst = jnp.argsort(jnp.where(mask, -mind, -0.0))[:k]
        empty = (cnts == 0) & cmask
        new_c = jnp.where(empty[:, None], xp[worst], new_c)
        obj = jnp.sum(jnp.where(mask, mind, 0.0))
        return new_c, obj

    c, objs = jax.lax.scan(step, init_c, None, length=iters)
    d = dists(c)
    assign = jnp.argmin(d, axis=1).astype(jnp.int32)
    return c, assign, objs


def kmeans(x: np.ndarray, k: int, iters: int = 10, seed: int = 0,
           init: str = "random") -> Tuple[np.ndarray, np.ndarray]:
    """Host-friendly k-means.  x (n, d) numpy -> (centroids (k,d),
    assignments (n,)).  Pads n to a power-of-2 bucket for jit-cache reuse."""
    n, d = x.shape
    k = min(k, n)
    rng = np.random.default_rng(seed)
    npad = _next_pow2(max(n, 8))
    xp = np.zeros((npad, d), dtype=np.float32)
    xp[:n] = x
    mask = np.zeros(npad, dtype=bool)
    mask[:n] = True

    if init == "pp":
        init_c = _kmeanspp_init(x, k, rng)
    else:
        init_c = x[rng.choice(n, size=k, replace=False)].astype(np.float32)

    c, assign, _ = _lloyd(jnp.asarray(xp), jnp.asarray(mask),
                          jnp.asarray(init_c), jnp.ones(k, bool), k, iters)
    # one pull for both; np.array (not asarray): jax buffers are
    # read-only and callers mutate.  Slice on host: slicing the device
    # array would compile a new program for every distinct n (one per
    # maintenance split on the chip)
    c, assign = jax.device_get((c, assign))
    return np.array(c), np.array(assign)[:n]


def _kmeanspp_init(x: np.ndarray, k: int, rng: np.random.Generator
                   ) -> np.ndarray:
    """D^2-sampling seeding (host loop; only used at index build)."""
    n = x.shape[0]
    centroids = [x[rng.integers(n)]]
    d2 = np.sum((x - centroids[0]) ** 2, axis=1)
    for _ in range(1, k):
        probs = d2 / max(d2.sum(), 1e-12)
        idx = rng.choice(n, p=probs)
        centroids.append(x[idx])
        d2 = np.minimum(d2, np.sum((x - centroids[-1]) ** 2, axis=1))
    return np.stack(centroids).astype(np.float32)


def split_two(x: np.ndarray, iters: int = 8, seed: int = 0
              ) -> Tuple[np.ndarray, np.ndarray]:
    """2-means split of one partition (paper §4.2.1 Split).  Returns
    (2 centroids, assignment in {0,1})."""
    if x.shape[0] < 2:
        raise ValueError("cannot split a partition with < 2 vectors")
    c, a = kmeans(x, 2, iters=iters, seed=seed)
    # Guard: if 2-means degenerated to one side, force a median split along
    # the principal axis so the split is always well-defined.
    if (a == 0).all() or (a == 1).all():
        center = x.mean(0)
        xc = x - center
        # power iteration for the principal direction (cheap, host-side)
        v = np.ones(x.shape[1], dtype=np.float64)
        for _ in range(8):
            v = xc.T @ (xc @ v)
            v /= max(np.linalg.norm(v), 1e-12)
        proj = xc @ v
        a = (proj > np.median(proj)).astype(np.int32)
        if (a == 0).all() or (a == 1).all():  # all projections equal
            a = (np.arange(x.shape[0]) % 2).astype(np.int32)
        c = np.stack([x[a == 0].mean(0), x[a == 1].mean(0)]).astype(np.float32)
    return c, a


_ASSIGN_HOST_MAX = 1 << 22   # n*p below this: host GEMM path


def assign(x: np.ndarray, centroids: np.ndarray,
           impl: str = "auto") -> np.ndarray:
    """Nearest-centroid assignment via the fused kernel.

    Maintenance-sized problems (merge verifies, refine reassignment,
    insert routing — arbitrary, constantly changing (n, p) shapes) take
    a host GEMM instead: the jitted kernel would pay a fresh XLA compile
    for nearly every novel shape, which dominates the maintenance pass
    wall time on CPU.  Large builds still go through the kernel."""
    if (impl == "auto" and not ops._on_tpu()
            and x.shape[0] * centroids.shape[0] <= _ASSIGN_HOST_MAX):
        xs = np.asarray(x, dtype=np.float32)
        c = np.asarray(centroids, dtype=np.float32)
        d = np.sum(c * c, axis=1)[None, :] - 2.0 * (xs @ c.T)
        return np.argmin(d, axis=1).astype(np.int32)
    a, _ = ops.kmeans_assign(jnp.asarray(x, jnp.float32),
                             jnp.asarray(centroids, jnp.float32), impl=impl)
    return np.asarray(a)


def refine(parts: list, centroids: np.ndarray, iters: int = 1,
           ) -> Tuple[np.ndarray, list]:
    """Partition refinement (paper §4.2.1): k-means seeded by the current
    centroids over the union of the given partitions' vectors, then
    reassignment.  ``parts`` is a list of (vectors (s_j, d), ids (s_j,))
    aligned with ``centroids`` rows.  Returns (new_centroids, new_parts).
    """
    xs = np.concatenate([p[0] for p in parts], axis=0)
    ids = np.concatenate([p[1] for p in parts], axis=0)
    k, d = centroids.shape
    n = xs.shape[0]
    # the group size varies with every split: pad the centroids to a
    # multiple of 16 (masked) so the compiled Lloyd step is reused
    kpad = -(-k // 16) * 16
    npad = _next_pow2(max(n, kpad))
    xp = np.zeros((npad, d), dtype=np.float32)
    xp[:n] = xs
    mask = np.zeros(npad, dtype=bool)
    mask[:n] = True
    cp = np.zeros((kpad, d), dtype=np.float32)
    cp[:k] = centroids
    c, a, _ = _lloyd(jnp.asarray(xp), jnp.asarray(mask), jnp.asarray(cp),
                     jnp.asarray(np.arange(kpad) < k), kpad, iters)
    c = np.array(c)[:k]
    a = np.array(a)[:n]
    new_parts = []
    for j in range(k):
        sel = a == j
        new_parts.append((xs[sel], ids[sel]))
        if not sel.any():
            c[j] = centroids[j]  # keep old centroid for a (now) empty part
    return c, new_parts

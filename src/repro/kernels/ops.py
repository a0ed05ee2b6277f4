"""Jit'd public wrappers around the scan kernels.

Dispatch policy (``impl``):
  - "jnp":    pure-jnp oracle path (XLA fuses it well on CPU).
  - "pallas": the Pallas kernels. On CPU they execute in interpret mode
              (correctness path); on TPU they compile via Mosaic.
  - "auto":   pallas on TPU, jnp otherwise.

All wrappers handle padding to kernel tile alignments and un-padding of
results, so callers never see alignment constraints.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from .ref import MASK_DIST
from .kmeans_assign import kmeans_assign_pallas
from .scan_topk import scan_topk_pallas
from .scan_topk_indexed import (quantize_int8, scan_topk_indexed_pallas,
                                scan_topk_indexed_q8_pallas)

Array = jax.Array


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(impl: str) -> str:
    if impl == "auto":
        return "pallas" if _on_tpu() else "jnp"
    return impl


def _pad_to(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def scan_topk(queries: Array, xs: Array, k: int, *, metric: str = "l2",
              valid: Optional[Array] = None, impl: str = "auto",
              block_q: int = 128, block_s: int = 512,
              ) -> Tuple[Array, Array]:
    """Top-k nearest of each query against ``xs``.

    Returns (dists (Q, k) ascending, idx (Q, k) int32).  ``dists`` are true
    squared-L2 / negated-IP values (minimization convention); padded misses
    are MASK_DIST with idx -1.
    """
    impl = _resolve(impl)
    k_eff = min(k, xs.shape[0])
    if impl == "jnp":
        d, i = ref.scan_topk_ref(queries, xs, k_eff, metric, valid)
    else:
        d, i = _scan_topk_pallas_padded(queries, xs, k_eff, metric, valid,
                                        block_q, block_s)
    if k_eff < k:  # pad result columns up to k
        padd = jnp.full((d.shape[0], k - k_eff), MASK_DIST, d.dtype)
        padi = jnp.full((i.shape[0], k - k_eff), -1, i.dtype)
        d = jnp.concatenate([d, padd], axis=1)
        i = jnp.concatenate([i, padi], axis=1)
    return d, i


@functools.partial(jax.jit,
                   static_argnames=("k", "metric", "block_q", "block_s"))
def _scan_topk_pallas_padded(queries, xs, k, metric, valid, block_q, block_s):
    Q, d = queries.shape
    N, _ = xs.shape
    block_s = min(block_s, max(128, _next_pow2(N)))
    block_q = min(block_q, max(8, _pad_to(Q, 8)))
    Qp, Np = _pad_to(Q, block_q), _pad_to(N, block_s)
    k_pad = min(_next_pow2(max(k, 1)), block_s)

    qp = jnp.zeros((Qp, d), queries.dtype).at[:Q].set(queries)
    xp = jnp.zeros((Np, d), xs.dtype).at[:N].set(xs)
    ok = jnp.zeros((Np,), jnp.bool_).at[:N].set(
        jnp.ones((N,), jnp.bool_) if valid is None else valid)
    bias = jnp.where(ok, 0.0, MASK_DIST)
    if metric == "l2":
        aux = (jnp.sum(xp.astype(jnp.float32) ** 2, axis=-1) + bias)[None, :]
    else:
        aux = bias[None, :]

    dd, ii = scan_topk_pallas(qp, xp, aux, k_pad=k_pad, metric=metric,
                              block_q=block_q, block_s=block_s,
                              interpret=not _on_tpu())
    dd, ii = dd[:Q, :k], ii[:Q, :k]
    if metric == "l2":  # add back per-query ||q||^2 (kernel omits it)
        q2 = jnp.sum(queries.astype(jnp.float32) ** 2, axis=-1, keepdims=True)
        dd = jnp.where(dd >= MASK_DIST, dd, jnp.maximum(dd + q2, 0.0))
    ii = jnp.where(dd >= MASK_DIST, -1, ii)
    return dd, ii


def pack_union(selected: Array, n_union: int,
               priority: Optional[Array] = None) -> Tuple[Array, Array]:
    """Pack per-query partition selections into one static union scan plan.

    ``selected`` (B, P) bool — query b wants partition p.  Returns
    (sel (n_union,) int32 partition ids, qmask (B, n_union) bool) for
    ``scan_selected_topk``: the union covers every partition any query
    selected, and ``qmask`` restores per-query probe semantics inside the
    shared scan.

    The union is **frequency-ranked**: partitions are taken in descending
    order of how many queries probe them, so when ``n_union`` truncates
    the union (a ``union_cap`` under read skew — hot partitions dedupe
    across the batch) the scan keeps the partitions that serve the most
    queries and drops only the rarely-probed tail.  Uncapped, the ranking
    is irrelevant (every probed partition gets a slot; surplus slots take
    unprobed partitions under an all-False mask — inert).

    ``priority`` (P,) int32 is added to the per-partition probe counts
    before ranking.  Callers use it as the *anchor guarantee*: boosting
    every partition that is some query's nearest probe by more than B
    ranks all anchors above all non-anchors, so a cap sheds only
    non-nearest "insurance" probes and no query loses its best partition
    (until the cap is smaller than the number of distinct anchors, at
    which point anchors rank among themselves by frequency).

    This is the packed-scan planning primitive shared by the sharded
    engine (per shard) and the host-side batched executor
    (``core.multiquery``): one partition read serves every query in the
    batch that probes it.
    """
    counts = jnp.sum(selected, axis=0, dtype=jnp.int32)
    if priority is not None:
        counts = counts + priority
    _, sel = jax.lax.top_k(counts, n_union)
    sel = sel.astype(jnp.int32)
    qmask = jnp.take(selected, sel, axis=1)
    return sel, qmask


@functools.partial(jax.jit, static_argnames=("p", "n_union"))
def pack_round(sel_q: Array, qvalid: Array, priority: Array, *,
               p: int, n_union: int) -> Tuple[Array, Array]:
    """Round-aware masked pack: one probe-round's worth of per-query
    selections -> a packed union scan plan.

    ``sel_q`` (B, W) holds the probe-list columns each query would scan
    this round; ``qvalid`` (B, W) masks them (False = column past the
    query's planned count, or the query already met its recall target —
    the early-exit live mask folds in here, so later rounds rank only
    *live* demand).  ``priority`` (P,) int32 feeds the anchor guarantee
    exactly like ``pack_union`` (pass zeros when uncapped).  Returns the
    same (sel (n_union,), qmask (B, n_union)) contract as ``pack_union``.
    """
    b = sel_q.shape[0]
    rows = jnp.broadcast_to(jnp.arange(b)[:, None], sel_q.shape)
    selected = jnp.zeros((b, p), jnp.bool_).at[rows, sel_q].max(qvalid)
    return pack_union(selected, n_union, priority=priority)


@functools.partial(jax.jit, static_argnames=("p", "u_pad"))
def pack_round_masked(sel_q: Array, qvalid: Array, priority: Array,
                      n_real, *, p: int, u_pad: int
                      ) -> Tuple[Array, Array]:
    """``pack_round`` with the inert-tail discipline applied on device.

    ``n_real`` (dynamic scalar — distinct values share one compiled
    executable) is the number of live union slots; slots at or past it
    duplicate ``sel[0]`` under an all-False mask, and when the static
    padded width ``u_pad`` exceeds the packable width ``min(u_pad, p)``
    the surplus columns are appended the same way.  This replaces the
    host-side pattern of pulling the packed plan back, mutating writable
    copies and re-uploading them — the plan never leaves the device.
    """
    n_dev = min(u_pad, p)
    sel, qmask = pack_round(sel_q, qvalid, priority, p=p, n_union=n_dev)
    live = jnp.arange(n_dev) < n_real
    sel = jnp.where(live, sel, sel[0])
    qmask = qmask & live[None, :]
    if u_pad > n_dev:
        b = qmask.shape[0]
        sel = jnp.concatenate(
            [sel, jnp.full((u_pad - n_dev,), sel[0], sel.dtype)])
        qmask = jnp.concatenate(
            [qmask, jnp.zeros((b, u_pad - n_dev), jnp.bool_)], axis=1)
    return sel, qmask


@functools.partial(jax.jit, static_argnames=("k",))
def topk_merge(dists_a: Array, idx_a: Array, dists_b: Array, idx_b: Array,
               k: int) -> Tuple[Array, Array]:
    """Device-resident merge of two per-query top-k candidate lists
    (ascending by distance; misses = MASK_DIST / -1).  The multi-round
    batched executor folds each round's scan output into its running
    top-k with this — the accumulator never leaves the device."""
    return ref.merge_topk(dists_a, idx_a, dists_b, idx_b, k)


def scan_selected_topk(queries: Array, data: Array, valid: Array,
                       sel: Array, qmask: Array, k: int, *,
                       metric: str = "l2", impl: str = "auto",
                       block_q: int = 128, block_s: int = 512,
                       ) -> Tuple[Array, Array]:
    """Top-k of each query over the union of selected partition blocks.

    queries (B, d); data (P, S, d); valid (P, S) bool; sel (U,) int32;
    qmask (B, U) bool (query b scans block u).  Returns ascending
    (dists (B, k), flat idx (B, k) = partition * S + slot).

    impl="pallas" streams each selected block from HBM exactly once
    (scalar-prefetch index map) — the memory-roofline-optimal scan;
    "jnp" is the gather-based oracle.
    """
    impl = _resolve(impl)
    B = queries.shape[0]
    S = data.shape[1]
    k_eff = min(k, sel.shape[0] * S)
    if impl == "jnp":
        d_out, i_out = ref.scan_selected_ref(queries, data, valid, sel,
                                             qmask, k_eff, metric)
    else:
        d_out, i_out = _scan_selected_pallas_padded(
            queries, data, valid, sel, qmask, k_eff, metric,
            block_q, block_s)
    if k_eff < k:
        padd = jnp.full((B, k - k_eff), MASK_DIST, d_out.dtype)
        padi = jnp.full((B, k - k_eff), -1, i_out.dtype)
        d_out = jnp.concatenate([d_out, padd], axis=1)
        i_out = jnp.concatenate([i_out, padi], axis=1)
    return d_out, i_out


@functools.partial(jax.jit,
                   static_argnames=("k", "metric", "block_q", "block_s"))
def _scan_selected_pallas_padded(queries, data, valid, sel, qmask, k,
                                 metric, block_q, block_s):
    B, dim = queries.shape
    P, S, _ = data.shape
    # block_s must be a power-of-2 divisor of S (snapshots align S_cap)
    bs = min(block_s, S)
    while S % bs or not (bs & (bs - 1)) == 0:
        bs //= 2
    assert bs >= 8, f"S_cap={S} has no usable pow2 tile; align the snapshot"
    bq = min(block_q, max(8, _pad_to(B, 8)))
    Bp = _pad_to(B, bq)
    k_pad = min(_next_pow2(max(k, 1)), bs)

    # queries ride in the data's storage dtype (bf16 storage -> bf16 MXU
    # operands with f32 accumulation); query traffic is negligible
    qp = jnp.zeros((Bp, dim), data.dtype).at[:B].set(
        queries.astype(data.dtype))
    bias = jnp.where(valid, 0.0, MASK_DIST)                 # (P, S)
    if metric == "l2":
        aux = jnp.sum(data.astype(jnp.float32) ** 2, axis=-1) + bias
    else:
        aux = bias
    qb = jnp.zeros((Bp, sel.shape[0]), jnp.float32).at[:B].set(
        jnp.where(qmask, 0.0, MASK_DIST))
    dd, ii = scan_topk_indexed_pallas(
        qp, data, aux, sel.astype(jnp.int32), qb, k_pad=k_pad,
        metric=metric, block_q=bq, block_s=bs, interpret=not _on_tpu())
    dd, ii = dd[:B, :k], ii[:B, :k]
    if metric == "l2":
        q2 = jnp.sum(queries.astype(jnp.float32) ** 2, axis=-1,
                     keepdims=True)
        dd = jnp.where(dd >= MASK_DIST, dd, jnp.maximum(dd + q2, 0.0))
    ii = jnp.where(dd >= MASK_DIST, -1, ii)
    return dd, ii


def scan_selected_topk_q8(queries: Array, data_codes: Array,
                          data_scales: Array, valid: Array, sel: Array,
                          qmask: Array, k: int, *, metric: str = "l2",
                          centroids: Optional[Array] = None,
                          block_q: int = 128, block_s: int = 512,
                          ) -> Tuple[Array, Array]:
    """int8 variant of ``scan_selected_topk`` (paper §8.2 compression):
    ``data_codes`` (P, S, d) int8 with per-slot ``data_scales`` (P, S).
    Queries are quantized per-row on entry; distances dequantize the
    int32 MXU product.  4x less scan traffic than f32.

    With ``centroids`` (P, d) the codes are interpreted as IVF residuals
    (x = c_j + s*codes): the exact f32 query-centroid dot is folded in
    per selected block, so quantization error only touches the residual
    term — near-f32 recall at int8 storage."""
    B = queries.shape[0]
    S = data_codes.shape[1]
    k_eff = min(k, sel.shape[0] * S)
    d_out, i_out = _scan_selected_q8_padded(
        queries, data_codes, data_scales, valid, sel, qmask, centroids,
        k_eff, metric, block_q, block_s)
    if k_eff < k:
        padd = jnp.full((B, k - k_eff), MASK_DIST, d_out.dtype)
        padi = jnp.full((B, k - k_eff), -1, i_out.dtype)
        d_out = jnp.concatenate([d_out, padd], axis=1)
        i_out = jnp.concatenate([i_out, padi], axis=1)
    return d_out, i_out


@functools.partial(jax.jit,
                   static_argnames=("k", "metric", "block_q", "block_s"))
def _scan_selected_q8_padded(queries, codes, scales, valid, sel, qmask,
                             centroids, k, metric, block_q, block_s):
    B, dim = queries.shape
    P, S, _ = codes.shape
    U = sel.shape[0]
    bs = min(block_s, S)
    while S % bs or not (bs & (bs - 1)) == 0:
        bs //= 2
    assert bs >= 8, f"S_cap={S} has no usable pow2 tile"
    bq = min(block_q, max(8, _pad_to(B, 8)))
    Bp = _pad_to(B, bq)
    k_pad = min(_next_pow2(max(k, 1)), bs)

    q_codes, q_scales = quantize_int8(queries)
    qp = jnp.zeros((Bp, dim), jnp.int8).at[:B].set(q_codes)
    qsp = jnp.zeros((Bp, 1), jnp.float32).at[:B, 0].set(q_scales)
    bias = jnp.where(valid, 0.0, MASK_DIST)
    scales32 = scales.astype(jnp.float32)
    # dequantized ||x_hat||^2 — self-consistent quantized geometry
    r2 = jnp.sum(codes.astype(jnp.float32) ** 2, axis=-1)     # (P, S)
    if centroids is not None:
        cents32 = centroids.astype(jnp.float32)
        cr = jnp.einsum("pd,psd->ps", cents32,
                        codes.astype(jnp.float32), precision=ref.HIGHEST)
        x2 = (jnp.sum(cents32 ** 2, axis=-1)[:, None]
              + 2.0 * scales32 * cr + scales32 ** 2 * r2)
        # exact f32 query . centroid term per selected block
        qc_full = jnp.matmul(queries.astype(jnp.float32),
                             jnp.take(cents32, sel, axis=0).T,
                             precision=ref.HIGHEST)           # (B, U)
    else:
        x2 = scales32 ** 2 * r2
        qc_full = jnp.zeros((B, U), jnp.float32)
    aux = (x2 + bias) if metric == "l2" else bias
    qcp = jnp.zeros((Bp, U), jnp.float32).at[:B].set(qc_full)
    qb = jnp.zeros((Bp, U), jnp.float32).at[:B].set(
        jnp.where(qmask, 0.0, MASK_DIST))
    dd, ii = scan_topk_indexed_q8_pallas(
        qp, qsp, codes, scales32, aux, qcp,
        sel.astype(jnp.int32), qb, k_pad=k_pad, metric=metric,
        block_q=bq, block_s=bs, interpret=not _on_tpu())
    dd, ii = dd[:B, :k], ii[:B, :k]
    if metric == "l2":
        q2 = jnp.sum(queries.astype(jnp.float32) ** 2, axis=-1,
                     keepdims=True)
        dd = jnp.where(dd >= MASK_DIST, dd, jnp.maximum(dd + q2, 0.0))
    ii = jnp.where(dd >= MASK_DIST, -1, ii)
    return dd, ii


def kmeans_assign(xs: Array, centroids: Array, *,
                  valid_centroids: Optional[Array] = None,
                  impl: str = "auto", block_n: int = 512, block_c: int = 128,
                  ) -> Tuple[Array, Array]:
    """Nearest-centroid assignment; returns (assign (N,), min_sq_dist (N,))."""
    impl = _resolve(impl)
    if impl == "jnp":
        d = ref.pairwise_l2_sq(xs, centroids)
        if valid_centroids is not None:
            d = jnp.where(valid_centroids[None, :], d, MASK_DIST)
        return jnp.argmin(d, axis=-1).astype(jnp.int32), jnp.min(d, axis=-1)
    return _kmeans_assign_pallas_padded(xs, centroids, valid_centroids,
                                        block_n, block_c)


@functools.partial(jax.jit, static_argnames=("block_n", "block_c"))
def _kmeans_assign_pallas_padded(xs, centroids, valid, block_n, block_c):
    N, d = xs.shape
    C, _ = centroids.shape
    block_n = min(block_n, _pad_to(N, 8))
    block_c = min(block_c, max(128, _pad_to(C, 128)))
    Np, Cp = _pad_to(N, block_n), _pad_to(C, block_c)
    xp = jnp.zeros((Np, d), xs.dtype).at[:N].set(xs)
    cp = jnp.zeros((Cp, d), centroids.dtype).at[:C].set(centroids)
    ok = jnp.zeros((Cp,), jnp.bool_).at[:C].set(
        jnp.ones((C,), jnp.bool_) if valid is None else valid)
    aux = (jnp.sum(cp.astype(jnp.float32) ** 2, axis=-1)
           + jnp.where(ok, 0.0, MASK_DIST))[None, :]
    a, dd = kmeans_assign_pallas(xp, cp, aux, block_n=block_n,
                                 block_c=block_c, interpret=not _on_tpu())
    a, dd = a[:N, 0], dd[:N, 0]
    x2 = jnp.sum(xs.astype(jnp.float32) ** 2, axis=-1)
    dd = jnp.maximum(dd + x2, 0.0)
    return a, dd

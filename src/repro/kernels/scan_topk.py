"""Pallas TPU kernel: fused partition scan + top-k (Quake's hot loop).

The paper's query path is memory-bound: scan megabytes of vectors per query,
keep a running top-k (Quake §2.3/§6 — AVX512 distance loops on x86).  The
TPU-native rethink:

* distances via the MXU — ``dist = aux - 2 q·x`` (L2, with ``aux = ||x||^2``)
  or ``aux - q·x`` (inner product, ``aux = mask bias``) computed on
  ``(TQ, d) x (d, TS)`` VMEM tiles.  The per-query constant ``||q||^2`` is
  rank-preserving and folded in *outside* the kernel, so the kernel does no
  per-query rescans.
* selection via a **bitonic network** over the lane axis — compare-exchange
  built from lane rotations, no data-dependent control flow (TPU has no
  efficient per-lane branching).  Each (TQ, TS) tile is bitonic-sorted in
  *descending* order, so its best keys are its last lanes; those are merged
  into the ascending running top-k scratch that lives in VMEM across the
  sequential grid dimension (elementwise min, then a bitonic merge — no
  reversal).  The running width is k_pad widened to 128 lanes.
* grid = (query_tiles, block_rows) with dimension_semantics
  (PARALLEL, ARBITRARY): block_rows iterates sequentially (innermost) so the
  running top-k scratch accumulates; query tiles parallelize across cores.

HBM traffic: each database block is read exactly once per query tile
(N*d*bytes per TQ queries) — the roofline-optimal single pass.  VMEM working
set per step: TQ*d + TS*d + TQ*TS + 2*TQ*max(k, 128) words; with the default
TQ=128, TS=512, d<=1536 this stays under ~2.5 MB (fits the ~16 MB VMEM of a
v5e core with headroom for double buffering).

Validated in interpret mode on CPU against ``ref.scan_topk_ref`` (tests sweep
shapes/dtypes/metrics), compiled for a described v5e by
``tests/test_tpu_compile.py``, and run on the chip by ``chip_smoke.py``.
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

Array = jax.Array


def _is_pow2(n: int) -> bool:
    return n > 0 and (n & (n - 1)) == 0


# ---------------------------------------------------------------------------
# Bitonic network over the lane (last) axis.
#
# Mosaic lowers lane rotations (``pltpu.roll``) and elementwise int32/f32
# arithmetic, but not reversals, lane-splitting reshapes or selects between
# boolean vectors.  So each compare-exchange stage fetches the partner
# ``l ^ j`` with two rotations, derives every direction mask from an integer
# lane iota, and combines predicates with ``&``/``|`` only.  Keys compare
# lexicographically on (distance, index): ties resolve to the smaller index,
# as ``lax.top_k`` does, and the network stays a permutation.
# ---------------------------------------------------------------------------

def mxu_precision(dtype):
    """Contract precision for an in-kernel dot: f32 operands at full f32
    precision (Mosaic's default rounds them to bf16 for the MXU, which
    moves a d=768 distance by ~1e-3 relative and reorders near ties).
    Other operand types take the default."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _log2(n: int) -> int:
    return n.bit_length() - 1


def _lex_less(ad: Array, ai: Array, bd: Array, bi: Array) -> Array:
    """(ad, ai) < (bd, bi) in (distance, index) order."""
    return (ad < bd) | ((ad == bd) & (ai < bi))


def _compare_exchange(d: Array, i: Array, j: int, k: int,
                      descending: bool) -> Tuple[Array, Array]:
    """One bitonic stage: element ``l`` meets its partner ``l ^ j``; the pair
    sorts ascending where bit ``k`` of ``l`` is 0 (flipped if
    ``descending``).  ``k`` equal to the axis length means one direction for
    the whole row (a merge stage).
    """
    n = d.shape[-1]
    ax = d.ndim - 1
    lane = jax.lax.broadcasted_iota(jnp.int32, d.shape, ax)
    # Read the rotation's direction off a rotated iota instead of assuming
    # it: ``fwd`` marks lanes whose partner the (n - j)-rotation delivered.
    fwd = pltpu.roll(lane, n - j, ax) == (lane ^ j)
    pd = jnp.where(fwd, pltpu.roll(d, n - j, ax), pltpu.roll(d, j, ax))
    pi = jnp.where(fwd, pltpu.roll(i, n - j, ax), pltpu.roll(i, j, ax))
    upper = (lane >> _log2(j)) & 1
    down = ((lane >> _log2(k)) & 1) ^ int(descending)
    keep_max = (upper ^ down) == 1      # this slot ends with the larger key
    keep_min = (upper ^ down) == 0
    take = ((keep_min & _lex_less(pd, pi, d, i))
            | (keep_max & _lex_less(d, i, pd, pi)))
    return jnp.where(take, pd, d), jnp.where(take, pi, i)


def bitonic_sort(d: Array, i: Array, descending: bool = False
                 ) -> Tuple[Array, Array]:
    """Bitonic sort along the last axis (power-of-2 length), carrying an
    index payload.  log2(n)*(log2(n)+1)/2 vectorized stages.
    """
    n = d.shape[-1]
    assert _is_pow2(n), n
    k = 2
    while k <= n:
        j = k // 2
        while j >= 1:
            d, i = _compare_exchange(d, i, j, k, descending)
            j //= 2
        k *= 2
    return d, i


def bitonic_merge(d: Array, i: Array) -> Tuple[Array, Array]:
    """Sort a bitonic sequence into ascending order along the last axis.
    log2(n) stages.
    """
    n = d.shape[-1]
    assert _is_pow2(n), n
    j = n // 2
    while j >= 1:
        d, i = _compare_exchange(d, i, j, n, False)
        j //= 2
    return d, i


def merge_sorted_topk(run_d: Array, run_i: Array, new_d: Array, new_i: Array,
                      ) -> Tuple[Array, Array]:
    """Ascending top-k of two (…, k) candidate lists: ``run`` ascending and
    ``new`` descending.  Their elementwise minimum is a bitonic sequence
    that holds the k smallest keys of both; one bitonic merge sorts it.
    """
    take = _lex_less(new_d, new_i, run_d, run_i)
    return bitonic_merge(jnp.where(take, new_d, run_d),
                         jnp.where(take, new_i, run_i))


def lane_width(k_pad: int, block_s: int) -> int:
    """Width of the running top-k held in VMEM: ``k_pad`` widened to a full
    128-lane vector register where the tile allows it."""
    return max(k_pad, min(128, block_s))


def tile_topk_update(run_d, run_i, dist: Array, idx: Array) -> None:
    """Fold one (TQ, TS) distance tile into the running top-k refs: sort the
    tile descending, so its best ``kw`` keys are its last lanes, then merge
    them into the ascending running list."""
    kw = run_d.shape[-1]
    d_sorted, i_sorted = bitonic_sort(dist, idx, descending=True)
    ts = dist.shape[-1]
    m_d, m_i = merge_sorted_topk(run_d[...], run_i[...],
                                 d_sorted[:, ts - kw:], i_sorted[:, ts - kw:])
    run_d[...] = m_d
    run_i[...] = m_i


# ---------------------------------------------------------------------------
# Kernel body
# ---------------------------------------------------------------------------

def _scan_topk_kernel(q_ref, x_ref, aux_ref, out_d_ref, out_i_ref,
                      run_d, run_i, *, coef: float, nblocks: int,
                      block_s: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        run_d[...] = jnp.full_like(run_d, MASK_DIST)
        run_i[...] = jnp.full_like(run_i, -1)

    q = q_ref[...]          # (TQ, d)
    x = x_ref[...]          # (TS, d)
    aux = aux_ref[...]      # (1, TS): ||x||^2 (+mask bias) or mask bias
    # MXU: (TQ, d) @ (d, TS). fp32 accumulation regardless of input dtype.
    qx = jax.lax.dot_general(
        q, x, (((1,), (1,)), ((), ())),
        precision=mxu_precision(x.dtype),
        preferred_element_type=jnp.float32)
    dist = aux.astype(jnp.float32) + coef * qx  # (TQ, TS)

    base = j * block_s
    idx = base + jax.lax.broadcasted_iota(jnp.int32, dist.shape, 1)

    tile_topk_update(run_d, run_i, dist, idx)

    @pl.when(j == nblocks - 1)
    def _write():
        out_d_ref[...] = run_d[...]
        out_i_ref[...] = run_i[...]


@functools.partial(
    jax.jit,
    static_argnames=("k_pad", "metric", "block_q", "block_s", "interpret"))
def scan_topk_pallas(queries: Array, xs: Array, aux: Array, *, k_pad: int,
                     metric: str = "l2", block_q: int = 128,
                     block_s: int = 512, interpret: bool = True,
                     ) -> Tuple[Array, Array]:
    """Fused scan+top-k.  Shapes must be pre-padded:

    queries: (Q, d), Q % block_q == 0
    xs:      (N, d), N % block_s == 0
    aux:     (1, N)  — ``||x||^2 + bias`` for L2, ``bias`` for IP, where bias
             is 0 for valid rows and MASK_DIST for padded rows.

    Returns ascending (dists (Q, k_pad), idx (Q, k_pad)); L2 dists omit the
    per-query ``||q||^2`` term (caller adds it back; rank-preserving).
    """
    assert _is_pow2(block_s) and _is_pow2(k_pad) and k_pad <= block_s
    Q, d = queries.shape
    N, _ = xs.shape
    assert Q % block_q == 0 and N % block_s == 0, (Q, N, block_q, block_s)
    nq, nb = Q // block_q, N // block_s
    kw = lane_width(k_pad, block_s)
    coef = -2.0 if metric == "l2" else -1.0

    kernel = functools.partial(_scan_topk_kernel, coef=coef,
                               nblocks=nb, block_s=block_s)
    out_d, out_i = pl.pallas_call(
        kernel,
        grid=(nq, nb),
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_s, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, block_s), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, kw), lambda i, j: (i, 0)),
            pl.BlockSpec((block_q, kw), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Q, kw), jnp.float32),
            jax.ShapeDtypeStruct((Q, kw), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, kw), jnp.float32),
            pltpu.VMEM((block_q, kw), jnp.int32),
        ],
        compiler_params=pallas_compat.compiler_params(
            dimension_semantics=(pallas_compat.PARALLEL,
                                 pallas_compat.ARBITRARY)),
        interpret=interpret,
        name="quake_scan_topk",
    )(queries, xs, aux)
    return out_d[:, :k_pad], out_i[:, :k_pad]

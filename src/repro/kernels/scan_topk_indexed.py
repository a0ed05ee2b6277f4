"""Pallas TPU kernel: *indexed* fused partition scan + top-k.

The sharded engine's hot loop scans a per-batch **selection** of partition
blocks out of the device-resident snapshot ``(P, S, d)``.  The baseline XLA
path must ``gather`` the selected blocks into a fresh buffer and then run a
GEMM over the copy — every scanned byte moves through HBM ~3x (gather read,
gather write, dot read; plus a layout copy the dot may insert).

This kernel removes the copy entirely: the selected partition indices are a
**scalar-prefetch operand**, so the BlockSpec ``index_map`` streams each
selected block HBM->VMEM exactly once, the MXU computes the distance tile,
and a bitonic network folds it into the running top-k held in VMEM scratch.
HBM traffic = U * S * d * bytes + (tiny) outputs — the roofline minimum for
scanning U partitions.

Per-query probe semantics are preserved by an optional ``(B, U)`` bias
(0 where query b selected block u, MASK_DIST otherwise), so the fused union
scan returns *exactly* the same top-k as the per-query gather path.

Grid: ``(q_tiles, U, S/TS)`` with dimension_semantics
(PARALLEL, ARBITRARY, ARBITRARY) — the two sequential axes walk selected
blocks and their sub-tiles while the running top-k scratch persists.

Per-slot operands (``aux``, int8 scales) travel as ``(P, 1, S)`` so a
``(1, 1, TS)`` block meets Mosaic's (8, 128) tiling rule; per-query
operands (qmask bias, ``qc``) travel as whole ``(TQ, U)`` rows and the
kernel picks column ``u`` with a one-hot lane reduction.

Validated in interpret mode against ``ref.scan_selected_ref`` (tests sweep
shapes/selection patterns/metrics), compiled for a described v5e by
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
from .scan_topk import (_is_pow2, lane_width, mxu_precision,
                        tile_topk_update)

Array = jax.Array


def _column(ref, u) -> Array:
    """Column ``u`` of a (TQ, U) block as (TQ, 1), by a one-hot lane
    reduction (Mosaic has no dynamic lane slice)."""
    v = ref[...].astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    return jnp.sum(jnp.where(lane == u, v, 0.0), axis=1, keepdims=True)


def _scan_indexed_kernel(sel_ref, q_ref, x_ref, aux_ref, qmask_ref,
                         out_d_ref, out_i_ref, run_d, run_i, *,
                         coef: float, n_sel: int, n_sub: int,
                         block_s: int, s_cap: int):
    u = pl.program_id(1)
    s = pl.program_id(2)

    @pl.when((u == 0) & (s == 0))
    def _init():
        run_d[...] = jnp.full_like(run_d, MASK_DIST)
        run_i[...] = jnp.full_like(run_i, -1)

    q = q_ref[...]                      # (TQ, d)
    x = x_ref[0]                        # (TS, d)
    aux = aux_ref[0]                    # (1, TS): ||x||^2 (+pad bias) or bias
    qb = _column(qmask_ref, u)          # (TQ, 1): per-query selection bias
    qx = jax.lax.dot_general(
        q, x, (((1,), (1,)), ((), ())),
        precision=mxu_precision(x.dtype),
        preferred_element_type=jnp.float32)      # MXU (TQ, TS)
    dist = aux.astype(jnp.float32) + coef * qx + qb

    part = sel_ref[u]                   # selected partition id (scalar)
    base = part * s_cap + s * block_s
    idx = base + jax.lax.broadcasted_iota(jnp.int32, dist.shape, 1)
    tile_topk_update(run_d, run_i, dist, idx)

    @pl.when((u == n_sel - 1) & (s == n_sub - 1))
    def _write():
        out_d_ref[...] = run_d[...]
        out_i_ref[...] = run_i[...]


@functools.partial(
    jax.jit,
    static_argnames=("k_pad", "metric", "block_q", "block_s", "interpret"))
def scan_topk_indexed_pallas(queries: Array, data: Array, aux: Array,
                             sel: Array, qmask: Array, *, k_pad: int,
                             metric: str = "l2", block_q: int = 128,
                             block_s: int = 512, interpret: bool = True,
                             ) -> Tuple[Array, Array]:
    """Fused selected-block scan + top-k.  Shapes (pre-padded):

    queries: (B, d), B % block_q == 0
    data:    (P, S, d), S % block_s == 0
    aux:     (P, S)    — ``||x||^2 + pad_bias`` (L2) or ``pad_bias`` (IP)
    sel:     (U,) int32 — partition ids to scan (scalar-prefetched)
    qmask:   (B, U) f32 — 0 where query b wants block u, MASK_DIST otherwise
             (pass zeros to let every query see every selected block)

    Returns ascending (dists (B, k_pad), flat idx (B, k_pad)) where idx is
    ``partition * S + slot``; L2 dists omit ``||q||^2`` (caller adds back).
    """
    assert _is_pow2(block_s) and _is_pow2(k_pad) and k_pad <= block_s
    B, d = queries.shape
    P, S, _ = data.shape
    U = sel.shape[0]
    assert B % block_q == 0 and S % block_s == 0, (B, S, block_q, block_s)
    nq, ns = B // block_q, S // block_s
    coef = -2.0 if metric == "l2" else -1.0

    kw = lane_width(k_pad, block_s)
    kernel = functools.partial(
        _scan_indexed_kernel, coef=coef, n_sel=U, n_sub=ns,
        block_s=block_s, s_cap=S)
    grid_spec = pallas_compat.prefetch_scalar_grid_spec(
        num_scalar_prefetch=1,
        grid=(nq, U, ns),
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, u, s, sel_r: (i, 0)),
            pl.BlockSpec((1, block_s, d),
                         lambda i, u, s, sel_r: (sel_r[u], s, 0)),
            pl.BlockSpec((1, 1, block_s),
                         lambda i, u, s, sel_r: (sel_r[u], 0, s)),
            pl.BlockSpec((block_q, U), lambda i, u, s, sel_r: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, kw), lambda i, u, s, sel_r: (i, 0)),
            pl.BlockSpec((block_q, kw), lambda i, u, s, sel_r: (i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, kw), jnp.float32),
            pltpu.VMEM((block_q, kw), jnp.int32),
        ],
    )
    out_d, out_i = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, kw), jnp.float32),
            jax.ShapeDtypeStruct((B, kw), jnp.int32),
        ],
        compiler_params=pallas_compat.compiler_params(
            dimension_semantics=(pallas_compat.PARALLEL,
                                 pallas_compat.ARBITRARY,
                                 pallas_compat.ARBITRARY)),
        interpret=interpret,
        name="quake_scan_topk_indexed",
    )(sel, queries, data, aux.reshape(P, 1, S), qmask)
    return out_d[:, :k_pad], out_i[:, :k_pad]


# ---------------------------------------------------------------------------
# int8-quantized variant (paper §8.2 "Vector Compression", §Perf HC1 iter 5)
# ---------------------------------------------------------------------------

def _scan_indexed_q8_kernel(sel_ref, q_ref, qscale_ref, x_ref, scale_ref,
                            aux_ref, qc_ref, qmask_ref, out_d_ref,
                            out_i_ref, run_d, run_i, *, coef: float,
                            n_sel: int, n_sub: int, block_s: int,
                            s_cap: int):
    """Same scan, int8 codes: the MXU runs int8 x int8 -> int32 and the
    scalar product is dequantized with per-query x per-slot scales.  The
    dominant HBM stream (the vector codes) shrinks 4x vs f32.

    Residual (IVF-SQ8) form: codes encode x - c_j; the exact f32
    query-centroid dot rides in ``qc`` (per query x selected block) so
    only the small residual term carries quantization error:
        q.x = q.c_j + s_q * s_x * (q_i8 . r_i8).
    Plain form passes qc = 0.
    """
    u = pl.program_id(1)
    s = pl.program_id(2)

    @pl.when((u == 0) & (s == 0))
    def _init():
        run_d[...] = jnp.full_like(run_d, MASK_DIST)
        run_i[...] = jnp.full_like(run_i, -1)

    q = q_ref[...]                      # (TQ, d) int8 codes
    x = x_ref[0]                        # (TS, d) int8 codes
    aux = aux_ref[0]                    # (1, TS): dequantized ||x||^2 + bias
    qb = _column(qmask_ref, u)          # (TQ, 1)
    qc = _column(qc_ref, u)             # (TQ, 1) f32 q . c_{sel[u]}
    qs = qscale_ref[...]                # (TQ, 1) per-query dequant scale
    xs = scale_ref[0]                   # (1, TS) per-slot dequant scale
    qx_i = jax.lax.dot_general(
        q, x, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)        # MXU int8 path
    qx = qc + qx_i.astype(jnp.float32) * qs.astype(jnp.float32) \
        * xs.astype(jnp.float32)
    dist = aux.astype(jnp.float32) + coef * qx + qb

    part = sel_ref[u]
    base = part * s_cap + s * block_s
    idx = base + jax.lax.broadcasted_iota(jnp.int32, dist.shape, 1)
    tile_topk_update(run_d, run_i, dist, idx)

    @pl.when((u == n_sel - 1) & (s == n_sub - 1))
    def _write():
        out_d_ref[...] = run_d[...]
        out_i_ref[...] = run_i[...]


@functools.partial(
    jax.jit,
    static_argnames=("k_pad", "metric", "block_q", "block_s", "interpret"))
def scan_topk_indexed_q8_pallas(q_codes: Array, q_scales: Array,
                                data_codes: Array, data_scales: Array,
                                aux: Array, qc: Array, sel: Array,
                                qmask: Array, *,
                                k_pad: int, metric: str = "l2",
                                block_q: int = 128, block_s: int = 512,
                                interpret: bool = True,
                                ) -> Tuple[Array, Array]:
    """int8 indexed scan.  q_codes (B, d) int8 + q_scales (B, 1) f32;
    data_codes (P, S, d) int8 + data_scales (P, S) f32 (per-slot symmetric
    quantization); aux (P, S) = dequantized ||x||^2 + pad bias (L2) or pad
    bias (IP); qc (B, U) f32 = exact q . c_{sel[u]} for residual codes
    (zeros for plain codes).  Same return convention as
    ``scan_topk_indexed_pallas``."""
    assert _is_pow2(block_s) and _is_pow2(k_pad) and k_pad <= block_s
    B, d = q_codes.shape
    P, S, _ = data_codes.shape
    U = sel.shape[0]
    assert B % block_q == 0 and S % block_s == 0, (B, S, block_q, block_s)
    nq, ns = B // block_q, S // block_s
    coef = -2.0 if metric == "l2" else -1.0

    kw = lane_width(k_pad, block_s)
    kernel = functools.partial(
        _scan_indexed_q8_kernel, coef=coef, n_sel=U, n_sub=ns,
        block_s=block_s, s_cap=S)
    grid_spec = pallas_compat.prefetch_scalar_grid_spec(
        num_scalar_prefetch=1,
        grid=(nq, U, ns),
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, u, s, sel_r: (i, 0)),
            pl.BlockSpec((block_q, 1), lambda i, u, s, sel_r: (i, 0)),
            pl.BlockSpec((1, block_s, d),
                         lambda i, u, s, sel_r: (sel_r[u], s, 0)),
            pl.BlockSpec((1, 1, block_s),
                         lambda i, u, s, sel_r: (sel_r[u], 0, s)),
            pl.BlockSpec((1, 1, block_s),
                         lambda i, u, s, sel_r: (sel_r[u], 0, s)),
            pl.BlockSpec((block_q, U), lambda i, u, s, sel_r: (i, 0)),
            pl.BlockSpec((block_q, U), lambda i, u, s, sel_r: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, kw), lambda i, u, s, sel_r: (i, 0)),
            pl.BlockSpec((block_q, kw), lambda i, u, s, sel_r: (i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, kw), jnp.float32),
            pltpu.VMEM((block_q, kw), jnp.int32),
        ],
    )
    out_d, out_i = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, kw), jnp.float32),
            jax.ShapeDtypeStruct((B, kw), jnp.int32),
        ],
        compiler_params=pallas_compat.compiler_params(
            dimension_semantics=(pallas_compat.PARALLEL,
                                 pallas_compat.ARBITRARY,
                                 pallas_compat.ARBITRARY)),
        interpret=interpret,
        name="quake_scan_topk_indexed_q8",
    )(sel, q_codes, q_scales, data_codes, data_scales.reshape(P, 1, S),
      aux.reshape(P, 1, S), qc, qmask)
    return out_d[:, :k_pad], out_i[:, :k_pad]


def quantize_int8(x: Array, axis: int = -1) -> Tuple[Array, Array]:
    """Symmetric per-row int8 quantization: returns (codes, scales) with
    x ~= codes * scales[..., None]."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    codes = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return codes, scale[..., 0]


def quantize_int8_residual(data: Array, centroids: Array
                           ) -> Tuple[Array, Array]:
    """IVF-style residual quantization: codes encode ``x - c_j`` (the
    residual against the partition centroid), whose dynamic range is the
    cluster radius rather than the embedding norm — substantially finer
    int8 resolution at identical storage.  data (P, S, d), centroids
    (P, d); returns (codes (P, S, d) int8, scales (P, S))."""
    resid = data - centroids[:, None, :].astype(data.dtype)
    return quantize_int8(resid)

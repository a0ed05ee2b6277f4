"""Device-resident batched multi-query executor (paper §7.4, policy from
[26]/[34] — the incremental-IVF maintenance line of Mohoney et al.).

Single-query processing scans each needed partition once *per query*; with a
batch we invert the mapping — group queries by the partitions they access and
scan every needed partition exactly **once per batch**, amortizing the
partition read across all queries that probe it.  On TPU this turns B GEMVs
per partition into one ``(B_p, d) x (d, s)`` GEMM — MXU-shaped work.

Architecture (see ``docs/batched_execution.md``):

  1. **Plan**: per-query probe sets, either a fixed ``nprobe`` (the paper's
     Fig. 5 policy) or APS-driven per-query counts.  The APS planner is
     *vectorized*: one batched centroid-distance + top-``n_consider`` pass
     over the whole batch (``ops.scan_topk`` on device, or the equivalent
     host GEMM), the recall estimator run on ``(B, n_consider)`` arrays
     (``aps.estimate_probs_batch``), and the k-NN radius calibrated with a
     single batched sample search — no per-query Python loop.  The
     pre-vectorization loop survives as ``_aps_probe_counts_loop`` (the
     parity oracle and the bench baseline).
  2. **Pack**: the batch's probe sets collapse into one partition union +
     a per-query ``(B, U)`` mask through the device-side
     ``kernels.ops.pack_union`` primitive (frequency-ranked, so a
     ``union_cap`` keeps the hottest partitions under read skew — the
     batched-executor mirror of ``EngineConfig.union_cap``).
  3. **Scan** (device): calls to ``kernels.ops.scan_selected_topk`` —
     the scalar-prefetch ``scan_topk_indexed`` Pallas kernel streams each
     selected partition HBM->VMEM exactly once and folds the running top-k
     in VMEM (interpret mode on CPU CI, Mosaic on TPU; ``impl="jnp"`` is
     the XLA oracle path).  With ``storage_dtype="bf16"``/``"int8"`` the
     cached snapshot holds bf16 vectors / int8 IVF residual codes
     (``quantize_int8_residual``) and the scan streams 2x/4x fewer bytes
     through ``scan_selected_topk``/``scan_selected_topk_q8``.
  4. **Rounds** (Algorithm 2): APS-planned searches chunk the probe
     sequences into geometrically growing rounds (``run_round_loop``):
     each round packs only *live* queries' next probes (plus "union
     rides" — every not-yet-scanned probe landing in the round's union,
     so a partition block streams at most once per batch), folds the
     scan into a device-resident running top-k (``ops.topk_merge``),
     re-estimates per-query recall from the running k-th distance, and
     retires queries that cleared the target.  ``rounds=1`` degenerates
     to the monolithic fixed-plan scan.  The fully-jitted planner
     variant (``planner="fused"``, ``_fused_plan_probes``) runs centroid
     pass + estimator + selection in one jit with zero host round-trips
     in between — the TPU planner path.

Single-query search is the B=1 case of the same executor
(``per_query_search`` below, and ``QuakeIndex.search_batch`` with one row);
a partition-sharded snapshot (``layout``, below) runs the same plans with
a per-shard pack and scan and an all-gather merge; the mesh-sharded
engine's ``ShardedQuakeEngine.search_batch`` is this executor over its
mesh.

The executor serves a cached ``IndexSnapshot`` of the dynamic index
(copy-on-write semantics, paper §8.2), kept coherent through the index's
mutation journal: dirty-partition deltas patch only the touched rows on
device; structural changes (split/merge/level, capacity overflow) and int8
snapshots (rows would need requantizing) fall back to a full rebuild.  See
``docs/snapshot_lifecycle.md``.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, replace
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..kernels import ops
from ..kernels.ref import MASK_DIST
from ..obs.tracing import NO_SPAN, span
from . import aps as aps_mod
from .index import QuakeIndex
from .snapshot import (IndexSnapshot, PartitionLayout, merge_shards,
                       scatter_rows)

STORAGE_DTYPES = ("f32", "bf16", "int8")


@dataclass
class BatchResult:
    ids: np.ndarray        # (B, k) external ids, -1 on misses
    dists: np.ndarray      # (B, k) minimization convention, inf on misses
    partitions_scanned: int = 0   # partition blocks streamed (union size,
                                  # summed over rounds on the early-exit path)
    vectors_scanned: int = 0      # vectors streamed from memory: each union
                                  # partition is read once per round it
                                  # appears in
    comparisons: int = 0          # query-vector distance evaluations (the
                                  # per-query-loop equivalent of
                                  # vectors_scanned; ratio = amortization)
    nprobe: Optional[np.ndarray] = None   # (B,) effective probes per query
                                          # (== planned unless union-capped
                                          # or the query exited early)
    recall_estimate: Optional[np.ndarray] = None  # (B,) APS recall estimate
                                          # (planner cutoff estimate on the
                                          # fixed-plan path, refined running
                                          # estimate on the round path; NaN
                                          # where no radius was available;
                                          # None for nprobe-pinned searches)
    rounds: int = 1                       # probe rounds executed
    round_trace: Optional[dict] = None    # early-exit shape: per-round
                                          # live-query counts / vectors /
                                          # partitions / comparisons


@dataclass
class BatchPlan:
    """Output of the host-side batch planner."""
    sel: np.ndarray      # (U_pad,) union partition ids, frequency-ranked
                         # (tail entries duplicate sel[0] for tile-count
                         # padding and carry all-False masks)
    qmask: np.ndarray    # (B, U_pad) bool — query b probes union slot u
    nprobe: np.ndarray   # (B,) effective per-query probe count (probes
                         # surviving the union cap)
    n_real: int          # distinct partitions actually scanned
    planned: Optional[np.ndarray] = None  # (B,) pre-cap planned counts
    anchor: Optional[np.ndarray] = None   # (B,) each query's nearest
                                          # partition (cap-proof probes)
    recall_est: Optional[np.ndarray] = None  # (B,) planner recall estimate
                                          # at the planned cutoff (APS
                                          # planners only; NaN on fallback
                                          # rows with no radius)
    sel_dev: Optional[object] = None      # device residents of sel/qmask
    qmask_dev: Optional[object] = None    # (the executor scans these; the
                                          # host mirrors above are the
                                          # introspection/distribution
                                          # contract)


@dataclass
class RoundPlan:
    """Per-query probe *sequences* plus the estimator state the multi-round
    early-exit executor needs to re-score recall between rounds (Algorithm 2
    semantics for the host path).  All candidate arrays are aligned to the
    scan order: column 0 is the query's nearest partition, later columns
    descend by the planner's scan-probability ranking (an order that is
    invariant under the radius shrinking — cap fractions are monotone in
    the bisector margin for any rho)."""
    seq: np.ndarray         # (B, M) candidate partitions in scan order
    counts: np.ndarray      # (B,) planned probe counts (the fixed-plan
                            # budget; rounds chunk through seq[:, :count])
    geo: np.ndarray         # (B, M) seq-aligned geometry-space sq distances
    cc: np.ndarray          # (B, M) seq-aligned ||c_i - c_0|| distances
    recall_est: np.ndarray  # (B,) planner estimate at the planned cutoff
    seq_dev: Optional[object] = None  # device-resident int32 seq (set by
                            # the fused planner so the round executor
                            # never re-uploads what the device produced)


# ---------------------------------------------------------------------------
# Centroid passes (shared by the fixed-nprobe and APS planners)
# ---------------------------------------------------------------------------

def _centroid_dists(index: QuakeIndex, q: np.ndarray,
                    cent_norms: Optional[np.ndarray] = None) -> np.ndarray:
    """(B, P) level-0 centroid distances in scan-order convention
    (squared L2, or -score for IP — both rank like the geometry dists).
    ``cent_norms`` is the executor-cached ``||c||^2`` (recomputed only on
    snapshot refresh, not per call)."""
    cents = index.levels[0].centroids
    if index.config.metric == "l2":
        if cent_norms is None:
            cent_norms = np.sum(cents * cents, axis=1)
        return (np.sum(q * q, 1)[:, None] + cent_norms[None, :]
                - 2.0 * (q @ cents.T))
    return -(q @ cents.T)


def _centroid_geo_batch(index: QuakeIndex, q: np.ndarray,
                        cent_norms: Optional[np.ndarray] = None
                        ) -> np.ndarray:
    """(B, P) geometry-space squared centroid distances — the batched
    mirror of per-query ``index._centroid_geo_dists`` (MIPS-augmented
    space for IP, so the same cap machinery applies)."""
    if index.config.metric == "l2":
        # same expression as the fixed-path keys; one formula to keep
        # bitwise-consistent with the loop oracle
        return np.maximum(_centroid_dists(index, q, cent_norms), 0.0)
    s = q @ index.levels[0].centroids.T
    return np.maximum(np.sum(q * q, 1)[:, None] + index._max_norm_sq
                      - 2.0 * s, 0.0)


# ---------------------------------------------------------------------------
# Radius calibration
# ---------------------------------------------------------------------------

def _calib_sample(b: int) -> np.ndarray:
    return np.unique(np.linspace(0, b - 1, min(8, b)).astype(int))


def _calibrate_kth_loop(index: QuakeIndex, q: np.ndarray, k: int,
                        target: float) -> float:
    """Legacy calibration: one full host APS search per sample query (the
    pre-vectorization planner's dominant fixed cost — kept as the bench
    baseline)."""
    kths = []
    for s in _calib_sample(q.shape[0]):
        r = index.search(q[s], k, recall_target=target, record_stats=False)
        if len(r.dists):
            kths.append(float(r.dists[min(k, len(r.dists)) - 1]))
    return float(np.median(kths)) if kths else np.inf


_CALIB_NPROBE = 8   # per-sample probes for radius calibration: the kth
                    # distance within the 8 nearest partitions; an
                    # over-estimate of the true kth distance only inflates
                    # the radius, which makes the planner scan *more* —
                    # never less — so the approximation is recall-safe


def _calibrate_kth_batched(index: QuakeIndex, q: np.ndarray, k: int,
                           n_consider: int,
                           cache: Optional[PlannerCache] = None) -> float:
    """Amortized calibration: ONE batched sample search — every sample row
    is scanned against the union of the samples' top-``_CALIB_NPROBE``
    candidate partitions in a single GEMM over the index's resident
    buffers (no per-sample search loop).  Scanning a neighbour sample's
    partitions only tightens the estimate."""
    qs = q[_calib_sample(q.shape[0])]
    p = index.levels[0].num_partitions
    # cached norms are only valid while the cache's fingerprint is
    # current (maintenance refinement moves centroids without changing P)
    norms = None
    if cache is not None and cache._key == cache._fingerprint():
        norms = cache._cent_norms
    cd = _centroid_dists(index, qs, norms)
    n_cal = min(n_consider, _CALIB_NPROBE, p)
    if n_cal < p:
        probes = np.argpartition(cd, n_cal - 1, axis=1)[:, :n_cal]
        union = np.unique(probes)
    else:
        union = np.arange(p)
    lvl0 = index.levels[0]
    xs = [lvl0.vectors[j] for j in union]
    v = int(sum(len(x) for x in xs))
    if v == 0:
        return np.inf
    x = np.concatenate(xs)                                # (V, d)
    if index.config.metric == "l2":
        x2 = np.concatenate([lvl0.sqnorms[j] for j in union])
        d = (x2[None, :] - 2.0 * (qs @ x.T)
             + np.sum(qs * qs, 1)[:, None])
    else:
        d = -(qs @ x.T)
    kk = min(k, v)
    kth = np.partition(d, kk - 1, axis=1)[:, kk - 1]
    return float(np.median(kth.astype(np.float64)))


class PlannerCache:
    """Snapshot-fingerprinted planner state: cached centroid norms +
    calibrated APS radii, invalidated by the journal fingerprint.  The
    one implementation behind both serving paths — the
    ``BatchedSearchExecutor`` composes one, and the sharded engine's
    ``search_batch`` keeps its own — so the invalidation key can never
    diverge between them.

    Cached radii additionally expire after ``radius_ttl`` reuses: on a
    static index the fingerprint never moves, and a radius calibrated
    from one batch's sample can go stale if the *query* distribution
    drifts — the TTL bounds that staleness at ~1 recalibration per
    ``radius_ttl`` batches (amortized cost stays negligible).  The TTL
    defaults to ``QuakeConfig.planner_radius_ttl`` so serving stacks tune
    it in one place (executor and sharded-engine caches both flow through
    here); an explicit ``radius_ttl`` argument still overrides."""

    RADIUS_TTL = 64

    def __init__(self, index: QuakeIndex, radius_ttl: Optional[int] = None):
        self.index = index
        if radius_ttl is None:
            radius_ttl = getattr(index.config, "planner_radius_ttl",
                                 self.RADIUS_TTL)
        self.radius_ttl = radius_ttl
        self._key = None
        self._cent_norms = None
        self._kth_cache = {}     # (key, k, target) -> [kth_med, uses]
        self._dev = None         # fused-planner device residents

    def _fingerprint(self):
        return (self.index.version, self.index.num_partitions,
                self.index.num_vectors)

    def ensure_fresh(self):
        fp = self._fingerprint()
        if self._key != fp:
            cents = self.index.levels[0].centroids
            self._cent_norms = np.sum(cents * cents, axis=1)
            self._kth_cache = {}
            self._dev = None
            self._key = fp
        return self

    def device_arrays(self):
        """(centroids, MIPS augmentation extras, beta table) resident on
        device for the fused single-jit planner — uploaded once per
        snapshot fingerprint, not per batch."""
        if self._key != self._fingerprint() or self._dev is None:
            self.ensure_fresh()
            idx = self.index
            cents = jnp.asarray(idx.levels[0].centroids)
            if idx.config.metric == "ip":
                aug = jnp.asarray(
                    idx._augment_extra(0).astype(np.float32))
            else:
                aug = jnp.zeros((cents.shape[0],), jnp.float32)
            self._dev = (cents, aug, jnp.asarray(idx._beta_table))
        return self._dev

    def get_radius(self, k: int, target: float) -> Optional[float]:
        if self._key != self._fingerprint():
            return None
        entry = self._kth_cache.get((self._key, k, float(target)))
        if entry is None or entry[1] >= self.radius_ttl:
            return None
        entry[1] += 1
        return entry[0]

    def put_radius(self, k: int, target: float, kth_med: float) -> None:
        if self._key == self._fingerprint():
            self._kth_cache[(self._key, k, float(target))] = [kth_med, 0]


# ---------------------------------------------------------------------------
# APS probe planning: per-query loop (parity oracle) and vectorized
# ---------------------------------------------------------------------------

def _aps_candidate_budget(index: QuakeIndex) -> int:
    p = index.levels[0].num_partitions
    return min(max(int(np.ceil(index.aps_f_m * p)),
                   index.config.min_candidates), p)


def _aps_probe_counts_loop(index: QuakeIndex, q: np.ndarray, k: int,
                           target: float,
                           kth_med: Optional[float] = None,
                           geo: Optional[np.ndarray] = None,
                           ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The pre-vectorization planner: per-query Python loop (per-query
    centroid distances over all P, per-query argsort, scalar
    ``estimate_probs_np``, per-query cc distances) — the parity oracle
    for ``_aps_probe_counts_batched`` and the wall-time baseline in
    ``bench_multiquery --cell planner``.  Pass a shared ``geo`` matrix
    (``_centroid_geo_batch``) to pin parity bitwise — per-query GEMV and
    batched GEMM round differently.  Returns (sel (B, n_max),
    valid (B, n_max), per-query probe counts (B,))."""
    b = q.shape[0]
    p = index.levels[0].num_partitions
    n_consider = _aps_candidate_budget(index)
    if kth_med is None:
        kth_med = _calibrate_kth_loop(index, q, k, target)

    sel = np.zeros((b, n_consider), dtype=np.int64)
    valid = np.zeros((b, n_consider), dtype=bool)
    counts = np.empty(b, dtype=np.int64)
    table = index._beta_table
    for i in range(b):
        qi = q[i]
        geo_i = geo[i] if geo is not None else \
            index._centroid_geo_dists(qi, 0, np.arange(p))[0]
        order = np.argsort(geo_i, kind="stable")[:n_consider]
        rho_fn = index._rho_sq_from_item_dist(
            float(np.sum(qi.astype(np.float64) ** 2)))
        rho_sq = rho_fn(kth_med) if np.isfinite(kth_med) else np.inf
        if not np.isfinite(rho_sq) or rho_sq <= 0 or len(order) == 1:
            m = len(order)  # no radius: conservative full candidate scan
            probes = order
        else:
            cc = index._centroid_cc_dists(0, order, 0)
            vmask = np.ones(len(order), dtype=bool)
            vmask[0] = False
            p0, probs = aps_mod.estimate_probs_np(
                float(geo_i[order[0]]), geo_i[order].astype(np.float64),
                cc, rho_sq, table, vmask)
            if p0 >= target:
                m, probes = 1, order[:1]
            else:
                desc = np.argsort(-probs, kind="stable")
                desc = desc[desc != 0]     # nearest is always scanned
                r_cum = p0 + np.cumsum(probs[desc])
                reach = np.nonzero(r_cum >= target)[0]
                extra = (reach[0] + 1) if len(reach) else len(desc)
                m = int(min(1 + extra, len(order)))
                probes = np.concatenate([order[:1], order[desc[:m - 1]]])
        sel[i, :m] = probes
        valid[i, :m] = True
        counts[i] = m
    n_max = int(counts.max())
    return sel[:, :n_max], valid[:, :n_max], counts


def _aps_probe_counts_batched(index: QuakeIndex, q: np.ndarray, k: int,
                              target: float,
                              kth_med: Optional[float] = None,
                              geo: Optional[np.ndarray] = None,
                              cent_norms: Optional[np.ndarray] = None,
                              cache: Optional[PlannerCache] = None,
                              pass_impl: str = "numpy",
                              full: bool = False):
    """Vectorized APS planner: the whole batch planned with array ops.

    The centroid pass is either the host batched GEMM (``pass_impl=
    "numpy"`` — bitwise-parity path with the loop oracle) or one jitted
    ``ops.scan_topk`` call (``"scan_topk"`` — the device pass; same probe
    sets up to matmul rounding).  The estimator is
    ``aps.estimate_probs_batch`` on ``(B, n_consider)`` arrays; the k-NN
    radius comes from one batched sample search instead of up-to-8 host
    APS searches.  Returns ``_aps_probe_counts_loop``'s (sel, valid,
    counts) contract plus a fourth element — the per-query recall
    estimate at the planned cutoff (NaN on no-radius fallback rows).
    With ``full=True`` it instead returns the :class:`RoundPlan` the
    multi-round executor consumes (full scan-ordered candidate sequences
    plus seq-aligned estimator inputs).
    """
    b = q.shape[0]
    cfg = index.config
    m = _aps_candidate_budget(index)
    if kth_med is None:
        # steady-state serving amortizes calibration across batches: the
        # planner cache keys the radius on its snapshot fingerprint (with
        # a reuse TTL against query-distribution drift), re-checking the
        # fingerprint at lookup so a direct call against a
        # mutated-but-unrefreshed index never reuses a stale radius
        if cache is not None:
            kth_med = cache.get_radius(k, target)
        if kth_med is None:
            kth_med = _calibrate_kth_batched(index, q, k, m, cache=cache)
            if cache is not None:
                cache.put_radius(k, target, kth_med)

    cents = index.levels[0].centroids
    if pass_impl == "scan_topk":
        # one jitted centroid-distance + top-n_consider pass on device
        cd, order = ops.scan_topk(jnp.asarray(q), jnp.asarray(cents), m,
                                  metric=cfg.metric, impl="auto")
        # the batched APS estimator runs on host over the centroid pass
        # output, so the pass result is pulled once per plan
        # quakecheck: allow-sync(planner boundary pull for the host APS estimator)
        cd = np.asarray(cd, dtype=np.float64)
        order = np.asarray(order, dtype=np.int64)  # quakecheck: allow-sync(planner boundary pull)
        if cfg.metric == "l2":
            geo_sel = np.maximum(cd, 0.0)
        else:   # minimization keys are -score; lift into MIPS geometry
            q2 = np.sum(q.astype(np.float64) ** 2, axis=1)
            geo_sel = np.maximum(
                q2[:, None] + index._max_norm_sq + 2.0 * cd, 0.0)
    else:
        if geo is None:
            geo = _centroid_geo_batch(index, q, cent_norms)
        order = np.argsort(geo, axis=1, kind="stable")[:, :m]
        geo_sel = np.take_along_axis(geo, order, axis=1).astype(np.float64)

    # per-query radius in geometry space (same rho map as the loop)
    q_norm = np.sum(q.astype(np.float64) ** 2, axis=1)
    if np.isfinite(kth_med):
        if cfg.metric == "l2":
            rho_sq = np.full(b, max(float(kth_med), 0.0))
        else:
            rho_sq = np.maximum(
                q_norm + index._max_norm_sq + 2.0 * float(kth_med), 0.0)
    else:
        rho_sq = np.full(b, np.inf)
    fallback = ~np.isfinite(rho_sq) | (rho_sq <= 0) | (m == 1)

    if m > 1:
        # batched cc distances: ||c_i - c0|| per query in geometry space
        cg = cents[order].astype(np.float64)              # (B, M, d)
        d2 = np.sum((cg - cg[:, :1, :]) ** 2, axis=2)
        if cfg.metric == "ip":
            e = index._augment_extra(0)[order]            # (B, M)
            d2 = d2 + (e - e[:, :1]) ** 2
        cc = np.sqrt(np.maximum(d2, 0.0))

        valid = np.ones((b, m), dtype=bool)
        valid[:, 0] = False
        p0, probs = aps_mod.estimate_probs_batch(
            geo_sel[:, 0], geo_sel, cc, rho_sq, index._beta_table, valid)

        # probability-descending scan order (nearest always first); forcing
        # the nearest's key to +inf reproduces the loop's stable
        # argsort-then-drop exactly
        neg = -probs
        neg[:, 0] = np.inf
        desc = np.argsort(neg, axis=1, kind="stable")[:, :m - 1]
        r_cum = p0[:, None] + np.cumsum(
            np.take_along_axis(probs, desc, axis=1), axis=1)
        reached = r_cum >= target
        extra = np.where(reached.any(axis=1),
                         np.argmax(reached, axis=1) + 1, m - 1)
        counts = np.where(p0 >= target, 1, np.minimum(1 + extra, m))
        seq = np.concatenate(
            [order[:, :1], np.take_along_axis(order, desc, axis=1)], axis=1)
        r_at = np.take_along_axis(
            r_cum, np.maximum(counts - 2, 0)[:, None], axis=1)[:, 0]
        r_est = np.where(counts <= 1, p0, r_at)
    else:
        counts = np.ones(b, dtype=np.int64)
        seq = order
        r_est = np.full(b, np.nan)
    counts = np.where(fallback, m, counts).astype(np.int64)
    seq = np.where(fallback[:, None], order, seq)
    r_est = np.where(fallback, np.nan, r_est)

    if full:
        if m > 1:
            def _seq_align(a):
                return np.where(
                    fallback[:, None], a,
                    np.concatenate(
                        [a[:, :1], np.take_along_axis(a, desc, axis=1)],
                        axis=1))
            geo_seq = _seq_align(geo_sel)
            cc_seq = _seq_align(cc)
        else:
            geo_seq = geo_sel
            cc_seq = np.zeros((b, 1))
        return RoundPlan(seq=seq.astype(np.int64), counts=counts,
                         geo=geo_seq.astype(np.float64),
                         cc=cc_seq.astype(np.float64), recall_est=r_est)

    n_max = int(counts.max())
    vmask = np.arange(n_max)[None, :] < counts[:, None]
    sel = np.where(vmask, seq[:, :n_max], 0).astype(np.int64)
    return sel, vmask, counts, r_est


# ---------------------------------------------------------------------------
# Fused single-jit device planner (TPU planner path)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("m", "metric"))
def _fused_plan_probes(q, cents, aug_extra, max_norm_sq, kth_med, table,
                       target, *, m: int, metric: str):
    """The whole APS batch planner as ONE jitted function: centroid pass
    (``ops.scan_topk`` consumed directly on device), geometric beta-table
    lookup, recall estimation (``aps.estimate_probs_batch`` on jnp
    arrays) and probe *selection* (probability-descending cumulative
    cutoff at the recall target, candidate-budget clamping) — no host
    round-trip anywhere between the centroid pass and the selected probe
    sets.  The numpy planner (``_aps_probe_counts_batched``) is the
    parity oracle, exactly as the loop planner is for it.

    Returns (seq (B, M) int32 scan-ordered candidates, counts (B,) int32,
    recall_est (B,) f32, geo_seq (B, M), cc_seq (B, M)) — everything the
    round executor needs, still resident on device.
    """
    b = q.shape[0]
    cd, order = ops.scan_topk(q, cents, m, metric=metric, impl="auto")
    order = order.astype(jnp.int32)
    if metric == "l2":
        geo_sel = jnp.maximum(cd, 0.0)
        rho_sq = jnp.broadcast_to(jnp.maximum(kth_med, 0.0), (b,))
    else:   # minimization keys are -score; lift into MIPS geometry
        q2 = jnp.sum(q.astype(jnp.float32) ** 2, axis=1)
        geo_sel = jnp.maximum(q2[:, None] + max_norm_sq + 2.0 * cd, 0.0)
        rho_sq = jnp.maximum(q2 + max_norm_sq + 2.0 * kth_med, 0.0)
    rho_sq = jnp.where(jnp.isfinite(kth_med), rho_sq, jnp.inf)
    if m == 1:
        return (order, jnp.ones((b,), jnp.int32),
                jnp.full((b,), jnp.nan, jnp.float32), geo_sel,
                jnp.zeros((b, 1), jnp.float32))
    fallback = ~jnp.isfinite(rho_sq) | (rho_sq <= 0)

    cg = jnp.take(cents, order, axis=0)                   # (B, M, d)
    d2 = jnp.sum((cg - cg[:, :1, :]) ** 2, axis=2)
    if metric == "ip":
        e = jnp.take(aug_extra, order)                    # (B, M)
        d2 = d2 + (e - e[:, :1]) ** 2
    cc = jnp.sqrt(jnp.maximum(d2, 0.0))

    valid = jnp.ones((b, m), jnp.bool_).at[:, 0].set(False)
    p0, probs = aps_mod.estimate_probs_batch(
        geo_sel[:, 0], geo_sel, cc, rho_sq, table, valid)

    # probability-descending scan order (nearest always first); the +inf
    # key on the nearest reproduces the numpy argsort-then-drop exactly
    neg = (-probs).at[:, 0].set(jnp.inf)
    desc = jnp.argsort(neg, axis=1)[:, :m - 1]            # stable sort
    r_cum = p0[:, None] + jnp.cumsum(
        jnp.take_along_axis(probs, desc, axis=1), axis=1)
    reached = r_cum >= target
    extra = jnp.where(reached.any(axis=1),
                      jnp.argmax(reached, axis=1) + 1, m - 1)
    counts = jnp.where(p0 >= target, 1, jnp.minimum(1 + extra, m))
    counts = jnp.where(fallback, m, counts).astype(jnp.int32)

    def _seq_align(a):
        tail = jnp.take_along_axis(a, desc, axis=1)
        return jnp.where(fallback[:, None], a,
                         jnp.concatenate([a[:, :1], tail], axis=1))
    seq = _seq_align(order)
    geo_seq = _seq_align(geo_sel)
    cc_seq = _seq_align(cc)
    r_at = jnp.take_along_axis(
        r_cum, jnp.maximum(counts - 2, 0)[:, None], axis=1)[:, 0]
    r_est = jnp.where(counts <= 1, p0, r_at)
    r_est = jnp.where(fallback, jnp.nan, r_est).astype(jnp.float32)
    return seq, counts, r_est, geo_seq, cc_seq


def _aps_probe_counts_fused(index: QuakeIndex, q: np.ndarray, k: int,
                            target: float,
                            kth_med: Optional[float] = None,
                            cache: Optional[PlannerCache] = None,
                            full: bool = False):
    """Host wrapper for the fused device planner: radius calibration and
    cache lookups stay on host (identical policy to the numpy planner),
    then one ``_fused_plan_probes`` call plans the whole batch on device.
    Same return contracts as ``_aps_probe_counts_batched``."""
    b = q.shape[0]
    cfg = index.config
    m = _aps_candidate_budget(index)
    if kth_med is None:
        if cache is not None:
            kth_med = cache.get_radius(k, target)
        if kth_med is None:
            kth_med = _calibrate_kth_batched(index, q, k, m, cache=cache)
            if cache is not None:
                cache.put_radius(k, target, kth_med)
    if cache is not None:
        cents_d, aug_d, table_d = cache.device_arrays()
    else:
        cents_d = jnp.asarray(index.levels[0].centroids)
        aug_d = jnp.asarray(index._augment_extra(0).astype(np.float32)) \
            if cfg.metric == "ip" else \
            jnp.zeros((cents_d.shape[0],), jnp.float32)
        table_d = jnp.asarray(index._beta_table)
    seq_d, counts_d, r_d, geo_d, cc_d = _fused_plan_probes(
        jnp.asarray(q), cents_d, aug_d,
        np.float32(index._max_norm_sq), np.float32(kth_med), table_d,
        np.float32(target), m=m, metric=cfg.metric)

    # the planner contract (probe selection, round chunking, the host APS
    # re-estimator) is host-side — one pull per plan at this boundary
    # quakecheck: allow-sync(fused planner boundary: host plan contract)
    counts = np.asarray(counts_d, dtype=np.int64)
    seq = np.asarray(seq_d, dtype=np.int64)  # quakecheck: allow-sync(fused planner boundary)
    r_est = np.asarray(r_d, dtype=np.float64)  # quakecheck: allow-sync(fused planner boundary)
    if full:
        return RoundPlan(seq=seq, counts=counts,
                         geo=np.asarray(geo_d, dtype=np.float64),   # quakecheck: allow-sync(fused planner boundary)
                         cc=np.asarray(cc_d, dtype=np.float64),     # quakecheck: allow-sync(fused planner boundary)
                         recall_est=r_est,
                         seq_dev=seq_d.astype(jnp.int32))
    n_max = int(counts.max())
    vmask = np.arange(n_max)[None, :] < counts[:, None]
    sel = np.where(vmask, seq[:, :n_max], 0).astype(np.int64)
    return sel, vmask, counts, r_est


# ---------------------------------------------------------------------------
# Pack: probe sets -> partition union + per-query mask (device primitive)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("p", "n_union", "u_pad"))
def _pack_plan(sel_q, qvalid, nearest, n_real, *, p: int, n_union: int,
               u_pad: int):
    """Scatter per-query probe sets into a (B, P) selection matrix, pack
    it through the device-side ``pack_union`` primitive, and apply the
    inert-tail discipline on device: union slots at or past ``n_real``
    (a dynamic scalar — distinct values share one executable) duplicate
    slot 0 under an all-False mask, and the static bucket width ``u_pad``
    is reached by appending more such slots when it exceeds the packable
    width.  ``nearest`` (B,) anchors each query's nearest partition above
    the frequency ranking so a union cap never drops a query's best
    probe."""
    b = sel_q.shape[0]
    rows = jnp.broadcast_to(jnp.arange(b)[:, None], sel_q.shape)
    selected = jnp.zeros((b, p), jnp.bool_).at[rows, sel_q].max(qvalid)
    anchor = jnp.zeros((p,), jnp.bool_).at[nearest].set(True)
    sel, qmask = ops.pack_union(selected, n_union,
                                priority=anchor.astype(jnp.int32)
                                * (b + 1))
    live = jnp.arange(n_union) < n_real
    sel = jnp.where(live, sel, sel[0])
    qmask = qmask & live[None, :]
    if u_pad > n_union:
        sel = jnp.concatenate(
            [sel, jnp.full((u_pad - n_union,), sel[0], sel.dtype)])
        qmask = jnp.concatenate(
            [qmask, jnp.zeros((b, u_pad - n_union), jnp.bool_)], axis=1)
    return sel, qmask


def plan_batch(index: QuakeIndex, q: np.ndarray, k: int,
               nprobe: Optional[int] = None,
               recall_target: Optional[float] = None,
               u_bucket: int = 8,
               union_cap: Optional[int] = None,
               planner: str = "vectorized",
               cent_norms: Optional[np.ndarray] = None,
               cache: Optional[PlannerCache] = None) -> BatchPlan:
    """Plan one batched scan: per-query probe sets -> partition union +
    per-query mask.

    ``planner`` selects the APS probe planner: ``"vectorized"`` (default;
    the batched host implementation), ``"fused"`` (the single-jit device
    planner — centroid pass, estimator and selection in one jitted call)
    or ``"loop"`` (the per-query baseline).
    ``union_cap`` bounds the number of distinct partitions the batch scans:
    the union is frequency-ranked (``pack_union`` keeps the partitions most
    queries probe), so under read skew a cap well below B*nprobe drops only
    rarely-probed tail partitions — ``BatchPlan.nprobe`` reports the
    *effective* per-query probes after capping (``planned`` keeps the
    pre-cap counts).  ``u_bucket`` rounds the union size up so the jitted
    scan sees few distinct shapes (pad slots duplicate a real partition and
    carry an all-False mask — they add work, never wrong results).
    """
    b = q.shape[0]
    p = index.levels[0].num_partitions

    if b == 0:
        # empty batch: one inert pad slot, no query rows
        return BatchPlan(sel=np.zeros(1, dtype=np.int64),
                         qmask=np.zeros((0, 1), dtype=bool),
                         nprobe=np.zeros(0, dtype=np.int64), n_real=0,
                         planned=np.zeros(0, dtype=np.int64))

    r_est = None
    if nprobe is not None:
        cd = _centroid_dists(index, q, cent_norms)
        n = int(max(1, min(nprobe, p)))
        if n < p:
            sel_q = np.argpartition(cd, n - 1, axis=1)[:, :n]
        else:
            sel_q = np.broadcast_to(np.arange(p), (b, p)).copy()
        qvalid = np.ones((b, n), dtype=bool)
        counts = np.full(b, n, dtype=np.int64)
        nearest = np.argmin(cd, axis=1)
    else:
        target = recall_target if recall_target is not None \
            else index.config.recall_target
        if planner == "loop":
            sel_q, qvalid, counts = _aps_probe_counts_loop(
                index, q, k, target)
        elif planner == "fused":
            sel_q, qvalid, counts, r_est = _aps_probe_counts_fused(
                index, q, k, target, cache=cache)
        else:
            sel_q, qvalid, counts, r_est = _aps_probe_counts_batched(
                index, q, k, target, cent_norms=cent_norms, cache=cache)
        nearest = sel_q[:, 0]   # APS probe sequences lead with the nearest

    # ---- union + (B, U) mask via the device-side pack primitive ----
    hit = np.zeros(p, dtype=bool)
    hit[sel_q[qvalid]] = True
    n_hits = int(hit.sum())
    if union_cap:
        # floor the cap at the distinct-anchor count: the anchor priority
        # ranks every query's nearest partition first, so with this floor
        # no query ever loses its whole probe set to the cap (a cap below
        # the anchor count would otherwise return silent all-miss rows)
        n_anchor = int(len(np.unique(nearest)))
        n_real = min(n_hits, max(union_cap, n_anchor))
    else:
        n_real = n_hits
    n_real = max(n_real, 1)
    u_pad = max(-(-n_real // u_bucket) * u_bucket, 1)
    n_dev = min(u_pad, p)
    # bucket the probe-set width too: APS counts.max() varies per batch,
    # and an unbucketed width would retrace the jitted pack per batch
    # (pad columns carry qvalid=False — inert under the scatter)
    n_cols = sel_q.shape[1]
    c_pad = max(-(-n_cols // u_bucket) * u_bucket, 1)
    if c_pad > n_cols:
        sel_q = np.concatenate(
            [sel_q, np.zeros((b, c_pad - n_cols), dtype=sel_q.dtype)], 1)
        qvalid = np.concatenate(
            [qvalid, np.zeros((b, c_pad - n_cols), dtype=bool)], 1)
    # pack + inert-tail masking stay on device (n_real rides as a dynamic
    # scalar, so distinct cap/hit counts share one executable); the scan
    # consumes sel_d/qmask_d directly — no host round trip on the hot path
    sel_d, qmask_d = _pack_plan(jnp.asarray(sel_q), jnp.asarray(qvalid),
                                jnp.asarray(nearest), n_real, p=p,
                                n_union=n_dev, u_pad=u_pad)
    # the distributed engine and plan introspection read sel/qmask on
    # host: one read-only pull at the plan boundary, never re-uploaded
    # quakecheck: allow-sync(host plan mirror for distributed/introspection)
    sel = np.asarray(sel_d, dtype=np.int64)
    qmask = np.asarray(qmask_d)  # quakecheck: allow-sync(host plan mirror)
    eff = qmask[:, :n_real].sum(axis=1).astype(np.int64)
    if r_est is not None:
        # a cap that truncated a query's probes invalidates its planner
        # estimate (it was computed at the pre-cap cutoff) — report NaN
        # rather than overstate the achievable recall
        r_est = np.where(eff < counts, np.nan, r_est)
    return BatchPlan(sel=sel, qmask=qmask, nprobe=eff, n_real=n_real,
                     planned=counts, anchor=np.asarray(nearest,
                                                       dtype=np.int64),
                     recall_est=r_est, sel_dev=sel_d, qmask_dev=qmask_d)


# ---------------------------------------------------------------------------
# Multi-round early-exit execution (Algorithm 2 for the batched host path)
# ---------------------------------------------------------------------------

def plan_rounds(index: QuakeIndex, q: np.ndarray, k: int, target: float,
                planner: str = "vectorized",
                cache: Optional[PlannerCache] = None,
                cent_norms: Optional[np.ndarray] = None) -> RoundPlan:
    """APS probe planning for the multi-round executor: full scan-ordered
    candidate sequences plus the seq-aligned estimator inputs (geometry
    distances, center-center distances) the round loop re-scores recall
    with.  ``planner`` is ``"vectorized"`` (host) or ``"fused"`` (the
    single-jit device planner); the loop baseline has no round form."""
    if planner == "fused":
        return _aps_probe_counts_fused(index, q, k, target, cache=cache,
                                       full=True)
    return _aps_probe_counts_batched(index, q, k, target,
                                     cent_norms=cent_norms, cache=cache,
                                     full=True)


def _round_windows(n_max: int, rounds: Optional[int] = None):
    """Column windows [(c0, c1), ...] chunking a probe list of length
    ``n_max`` into geometrically growing rounds: single-probe windows
    while exits are most likely (Algorithm 2 exits concentrate within the
    first few probes — the per-probe exit checks are what the fixed plan
    lacks), then doubling windows so the hard tail amortizes dispatch.
    A ``rounds`` budget merges the tail into the final round, so the
    windows always cover the full planned list — ``rounds=1`` degenerates
    to one fixed-plan scan.  The served ``RoundScheduler`` gives these
    windows only to rows that can retire early (early exit or a latency
    budget); a fixed-plan row there takes its whole plan as one window."""
    wins, c0, w = [], 0, 1
    while c0 < n_max:
        wins.append((c0, min(c0 + w, n_max)))
        c0 += w
        if len(wins) >= 3:          # probe-at-a-time for probes 1..3
            w *= 2
    if rounds is not None and rounds >= 1 and len(wins) > rounds:
        wins = wins[:rounds - 1] + [(wins[rounds - 1][0], n_max)]
    return wins


def run_round_loop(plan: RoundPlan, k: int, target: float, table,
                   rho_fn, scan_round, *, rounds: Optional[int] = None,
                   k_keep: Optional[int] = None,
                   deadline_s: Optional[float] = None,
                   clock=None):
    """Algorithm 2 round driver, shared by the host batched executor and
    the sharded engine's ``search_batch``.

    Each round, every *live* query advances through the next window of
    its planned probe sequence; the window's partitions form the round's
    union, and every live query additionally consumes all of its
    not-yet-scanned probes that happen to land in that union ("union
    riding": a partition block is streamed at most once per batch — the
    round decomposition never re-streams what the monolithic scan would
    read once, so early exit can only shrink the footprint).
    ``scan_round(take, kept)`` packs and scans the round — ``take``
    (B, M) marks the probe-sequence cells consumed this round, ``kept``
    the union partition ids — and returns device ``(dists (B, k_keep),
    ids (B, k_keep), stats)``.

    The driver owns the device-resident running top-k
    (``ops.topk_merge``), pulls only the per-query k-th distance each
    round, re-estimates APS recall from that *running* radius
    (``aps.estimate_probs_batch`` over the plan's seq-aligned candidates,
    restricted to the still-live rows), and masks out queries whose
    estimate cleared the target — later rounds shrink to the hard tail.
    Queries whose top-k is not yet full never exit (no radius -> keep
    scanning, the same rule as the sequential Algorithm 1 loop).
    ``union_cap`` runs never reach this driver: the cap's footprint
    bound is defined as plan-level truncation, so capped searches take
    the one-shot fixed-plan scan (a per-round cap would re-bound each
    round separately and let the batch total exceed the cap).

    ``deadline_s`` is a wall-clock budget for the whole loop (measured
    by ``clock``, default ``time.perf_counter``): when it expires the
    loop stops *at the end of the current round* — at least one round
    always runs — and the still-live queries' running top-k is returned
    as-is (their partial results; ``trace["budget_expired"]`` /
    ``trace["timed_out_rows"]`` report that it happened).  This is the
    per-query latency-budget primitive the serving runtime's
    ``PARTIAL`` status is built on (docs/serving.md).

    Returns (top dists, top ids — both device, ascending — nprobe (B,),
    recall_est (B,), rounds executed, per-round trace dict, totals).
    """
    b, m = plan.seq.shape
    counts = plan.counts
    k_keep = k if k_keep is None else k_keep
    n_max = int(counts.max(initial=1))
    wins = _round_windows(n_max, rounds)
    td = jnp.full((b, k_keep), MASK_DIST, jnp.float32)
    ti = jnp.full((b, k_keep), -1, jnp.int32)
    live = np.ones(b, dtype=bool)
    r_est = np.asarray(plan.recall_est, dtype=np.float64).copy()
    scanned = np.zeros((b, m), dtype=bool)
    valid = np.ones((b, m), dtype=bool)
    valid[:, 0] = False
    cols = np.arange(m)[None, :]
    within = cols < counts[:, None]
    p_hi = int(plan.seq.max()) + 1
    # the pinned per-round trace schema (docs/observability.md; a test
    # in tests/test_observability.py asserts these exact keys): parallel
    # per-round lists plus two scalar outcome flags — the serving trace
    # emitter and benchmarks/common.round_trajectory both rely on it
    trace = {"round_live": [], "round_partitions": [],
             "round_vectors": [], "round_comparisons": [],
             "round_kth": [], "round_wall_s": [],
             "budget_expired": False, "timed_out_rows": 0}
    clock = clock or time.perf_counter
    t0 = clock()
    n_rounds = 0
    for c0, c1 in wins:
        if not live.any():
            break
        if (deadline_s is not None and n_rounds > 0
                and clock() - t0 >= deadline_s):
            # budget spent: retire at the end of the last completed
            # round with the running top-k (partial results)
            trace["budget_expired"] = True
            trace["timed_out_rows"] = int(live.sum())
            break
        avail = live[:, None] & within & ~scanned
        base = avail & (cols >= c0) & (cols < c1)
        if not base.any():
            continue          # window already consumed by riding
        kept = np.unique(plan.seq[base])
        in_union = np.zeros(p_hi, dtype=bool)
        in_union[kept] = True
        take = avail & in_union[plan.seq]
        scanned |= take
        n_rounds += 1
        t_round = clock()
        trace["round_live"].append(int(live.sum()))
        d, i, st = scan_round(take, kept)
        td, ti = ops.topk_merge(td, ti, d, i, k_keep)
        for key in ("partitions", "vectors", "comparisons"):
            trace[f"round_{key}"].append(int(st[key]))
        # refined recall estimate from the *running* k-th distance —
        # live rows only; exited rows' estimates are frozen
        rows = np.nonzero(live)[0]
        # quakecheck: allow-sync(Algorithm 2's per-round kth-distance pull: the early-exit recall re-estimate is host-side by design)
        kth = np.asarray(td[rows, k - 1], dtype=np.float64)
        full_heap = kth < MASK_DIST
        rho_sq = np.where(full_heap, rho_fn(kth, rows), np.inf)
        p0, probs = aps_mod.estimate_probs_batch(
            plan.geo[rows, 0], plan.geo[rows], plan.cc[rows], rho_sq,
            table, valid[rows])
        r = p0 + np.where(scanned[rows] & valid[rows], probs,
                          0.0).sum(axis=1)
        r_est[rows[full_heap]] = r[full_heap]
        live[rows[full_heap & (r >= target)]] = False
        # per-round running k-th distance (median over rows whose heap
        # is full) and round wall time — the topk_merge above already
        # synced, so kth is host data and this costs no extra pull
        trace["round_kth"].append(
            float(np.median(kth[full_heap])) if full_heap.any() else None)
        trace["round_wall_s"].append(clock() - t_round)
    stats = {k_: int(np.sum(v)) for k_, v in
             (("partitions", trace["round_partitions"]),
              ("vectors", trace["round_vectors"]),
              ("comparisons", trace["round_comparisons"]))}
    return (td, ti, scanned.sum(axis=1).astype(np.int64), r_est,
            n_rounds, trace, stats)


def _batch_rho_fn(index: QuakeIndex, q: np.ndarray):
    """Vectorized kth-item-distance -> squared-geometry-radius map for the
    round loop (the batched mirror of ``_rho_sq_from_item_dist``).  The
    returned callable takes (kth, rows) where ``rows`` selects the query
    rows ``kth`` corresponds to (the driver's live subset)."""
    if index.config.metric == "l2":
        return lambda kth, rows=None: aps_mod.rho_sq_batch(kth,
                                                           metric="l2")
    qn = np.sum(q.astype(np.float64) ** 2, axis=1)
    m2 = index._max_norm_sq
    return lambda kth, rows=None: aps_mod.rho_sq_batch(
        kth, metric="ip", q_norm_sq=qn if rows is None else qn[rows],
        max_norm_sq=m2)


def _check_fits_device(p: int, s_cap: int, d: int, largest: int,
                       shards: int = 1) -> None:
    """Refuse, before staging it, a dense snapshot larger than the
    devices' memory (each of ``shards`` devices holds ``p / shards``
    rows).  The block is staged in f32 whatever the storage dtype."""
    stats = jax.devices()[0].memory_stats() or {}
    limit = stats.get("bytes_limit")
    need = p * s_cap * d * 4 // shards
    if limit and need > limit:
        raise MemoryError(
            f"dense snapshot of {p} partitions x {s_cap} slots x d={d} "
            f"(f32) over {shards} device(s) needs {need / 1e9:.2f} GB on "
            f"each, more than the device's {limit / 1e9:.2f} GB; the "
            f"largest partition holds {largest} vectors")


class BatchedSearchExecutor:
    """Executes planned batches against a device-resident snapshot.

    The snapshot (dense ``(P, S_cap, d)`` + ids + sizes) is cached and kept
    coherent with the dynamic index through its mutation journal: content
    mutations confined to known partitions (insert/delete/refine) patch
    only the touched rows on device (``IndexSnapshot.apply_delta``, COW
    semantics — paper §8.2), while structural changes (split/merge/level),
    capacity overflow, or a dirty set larger than
    ``config.snapshot_max_dirty_frac * P`` fall back to a full rebuild.
    Full rebuilds allocate ``config.snapshot_headroom`` slack capacity so
    insert deltas rarely force a reshape.  Searches then run one packed
    union scan per batch.

    ``storage_dtype`` sets the scan storage format (paper §8.2 vector
    compression): ``"f32"`` (exact), ``"bf16"`` (2x less scan traffic,
    delta-refresh capable — patches cast on device), or ``"int8"`` (IVF
    residual SQ8 through ``scan_selected_topk_q8``, 4x less traffic;
    content deltas would need requantization, so any journal delta forces
    a full rebuild — the same policy as the sharded engine).

    ``layout`` (``snapshot.PartitionLayout``) shards the snapshot's
    partition axis over a mesh, round-robin (paper §6): each round packs,
    scans and keeps a top-k per shard inside one ``shard_map`` and merges
    the shards' lists over the mesh.  The host mirrors ``_flat_ids`` and
    ``_sizes`` follow the physical rows, so a flat scan index is one
    gather away from its id.  One shard (the default) is today's
    single-device snapshot and programs: no ``shard_map``, no collective.
    """

    def __init__(self, index: QuakeIndex, impl: str = "auto",
                 u_bucket: int = 8, headroom: Optional[float] = None,
                 max_dirty_frac: Optional[float] = None,
                 storage_dtype: str = "f32",
                 union_cap: Optional[int] = None,
                 planner: str = "vectorized",
                 int8_rerank: bool = True,
                 rounds: Optional[int] = None,
                 part_bucket: int = 1,
                 layout: Optional[PartitionLayout] = None):
        if storage_dtype not in STORAGE_DTYPES:
            raise ValueError(f"storage_dtype must be one of "
                             f"{STORAGE_DTYPES}, got {storage_dtype!r}")
        self.index = index
        self.impl = impl
        self.u_bucket = u_bucket
        self.layout = layout or PartitionLayout()
        self.shards = self.layout.shards
        self._round_fns = {}     # static shape -> jitted sharded round
        self.part_bucket = max(part_bucket, 1)  # snapshot partition-count
                                 # rounding: a maintenance split/merge that
                                 # stays within the bucket keeps every
                                 # (P, S_cap, d) scan operand shape — and
                                 # therefore every compiled scan — alive
                                 # across the rebuild (serving runtimes
                                 # set 32; 1 = exact count)
        self.storage_dtype = storage_dtype
        self.planner = planner
        self.rounds = rounds     # early-exit round budget for APS-planned
                                 # searches: None = as many geometric
                                 # rounds as the plan needs, 1 = the
                                 # monolithic fixed-plan scan
        self.int8_rerank = int8_rerank   # exact re-rank of the int8 scan's
                                         # top-2k from a host f32 mirror
                                         # (B*2k row gather — negligible
                                         # next to the scan)
        self._host_f32 = None            # (P*S_cap, d) mirror, int8 only
        cfg = index.config
        self.union_cap = cfg.union_cap if union_cap is None else union_cap
        self.headroom = cfg.snapshot_headroom if headroom is None \
            else headroom
        self.max_dirty_frac = cfg.snapshot_max_dirty_frac \
            if max_dirty_frac is None else max_dirty_frac
        self._snap = None
        self._key = None         # fingerprint the snapshot reflects
        self._valid = None       # (P, S_cap) bool, device
        self._flat_ids = None    # (P*S_cap,) host, physical row order
        self._sizes = None       # (P,) host, physical row order
        self.planner_cache = PlannerCache(index)  # centroid norms +
                                 # calibrated radii, fingerprint-keyed
                                 # (refreshed with the snapshot)
        self.full_rebuilds = 0   # refresh telemetry (tests / bench)
        self.delta_refreshes = 0
        self.last_scan = None    # operand shapes and arguments of the
                                 # last round scan (f32/bf16 storage): what
                                 # a check of the compiled program lowers

    def _fingerprint(self):
        return (self.index.version, self.index.num_partitions,
                self.index.num_vectors)

    @property
    def _cent_norms(self):
        return self.planner_cache._cent_norms

    def _refresh_host_mirrors(self):
        self.planner_cache.ensure_fresh()

    def refresh(self):
        """Full rebuild of the device snapshot from the dynamic index.

        The slot capacity is *sticky*: a rebuild never shrinks it below
        the previous snapshot's (a maintenance split that halves the
        largest partition would otherwise halve ``S_cap`` and invalidate
        every compiled scan shape, only for the next insert wave to grow
        it back).  Monotone capacity costs padded slack rows — which the
        headroom policy already accepts — and keeps the ``(P, S_cap, d)``
        operand shape, and therefore the compiled scans, alive across
        maintenance epochs."""
        import math as _math
        from .maintenance import fit_to_capacity
        fixed = fit_to_capacity(self.index, self.headroom)
        lvl0 = self.index.levels[0]
        max_sz = int(max((len(v) for v in lvl0.vectors), default=0))
        cap = max(int(_math.ceil(max_sz * max(self.headroom, 1.0))), 1)
        if self._snap is not None:
            cap = max(cap, int(self._snap.capacity))
        cap = fixed or cap
        pad_to = self.part_bucket
        if self.part_bucket > 1:
            # partition padding is sticky too, with 25% growth slack, so
            # a handful of maintenance splits never crosses the pad
            # boundary and re-shapes the scan operands
            pad_to = (-(-int(lvl0.num_partitions * 1.25)
                        // self.part_bucket) * self.part_bucket)
            if self._snap is not None:
                pad_to = max(pad_to, int(self._snap.num_partitions))
            # from_index treats pad_partitions_to as a rounding multiple:
            # the absolute-target usage here is only sound while the
            # target covers the live count (ceil(p/pad_to) == 1)
            pad_to = max(pad_to, lvl0.num_partitions)
        # every shard holds as many rows
        pad_to = -(-pad_to // self.shards) * self.shards
        _check_fits_device(-(-lvl0.num_partitions // pad_to) * pad_to,
                           IndexSnapshot.align_capacity(cap),
                           self.index.dim, max_sz, self.shards)
        # release the old snapshot before staging the new one: at chip
        # scale the two do not fit in device memory together
        self._snap = self._valid = None
        with span("snapshot.stage") as sp:
            if sp is not NO_SPAN:
                sp.set_metadata(shards=self.shards,
                                partitions=lvl0.num_partitions)
            snap = IndexSnapshot.from_index(self.index, capacity=cap,
                                            pad_partitions_to=pad_to,
                                            layout=self.layout)
        self._valid = snap.ids >= 0
        self._flat_ids = np.array(snap.ids).reshape(-1)
        self._sizes = np.array(snap.sizes)
        if self.storage_dtype == "bf16":
            snap = replace(snap, data=snap.data.astype(jnp.bfloat16))
        elif self.storage_dtype == "int8":
            from ..kernels.scan_topk_indexed import quantize_int8_residual
            if self.int8_rerank:
                self._host_f32 = np.array(snap.data).reshape(
                    -1, snap.data.shape[-1])
            codes, scales = quantize_int8_residual(snap.data, snap.centroids)
            snap = replace(snap, data=codes, scales=scales)
        self._snap = snap
        self._refresh_host_mirrors()
        self._key = self._fingerprint()
        self.full_rebuilds += 1
        return self._snap

    def footprint(self) -> dict:
        """Device footprint of the cached snapshot: the dense
        ``(P, S_cap, d)`` block next to the live vectors it holds (the
        rest is slot and partition padding), its bytes on each device,
        and how it was kept fresh."""
        snap = self._snap
        if snap is None:
            return {}
        p, cap, d = snap.data.shape
        live = int(self._sizes.sum())
        return {"partitions": int(p), "capacity": int(cap), "dim": int(d),
                "dtype": str(snap.data.dtype),
                "device_bytes": int(snap.data.nbytes),
                "shards": self.shards,
                "device_bytes_per_device": [
                    int(sh.data.nbytes)
                    for sh in snap.data.addressable_shards],
                "live_vectors": live,
                "live_bytes": live * int(d) * snap.data.dtype.itemsize,
                "largest_partition": int(self._sizes.max(initial=0)),
                "full_rebuilds": self.full_rebuilds,
                "delta_refreshes": self.delta_refreshes}

    def _refresh_delta(self, delta) -> bool:
        """Patch the dirty partition rows in place of a rebuild.  Returns
        False when the delta is not applicable (structural change, capacity
        overflow, dirty set too large, or int8 storage — residual codes
        would need requantizing) — caller falls back to ``refresh``.
        """
        if self._snap.scales is not None:
            return False          # int8: requantize via full rebuild
        idx = self.index
        lvl0 = idx.levels[0]
        p_real = lvl0.num_partitions
        if delta.structural or p_real > self._snap.num_partitions:
            return False
        dirty = sorted(j for j in delta.dirty if j < p_real)
        if len(dirty) > self.max_dirty_frac * max(p_real, 1):
            return False
        if not dirty:
            # clock moved without base-level content changes (e.g. an
            # upper-level split): snapshot already coherent
            self._key = self._fingerprint()
            return True
        cap = self._snap.capacity
        if max(len(lvl0.vectors[j]) for j in dirty) > cap:
            return False      # a partition outgrew its slack slots
        try:
            patch = IndexSnapshot.build_patch(idx, dirty, cap)
            # donate: the executor owns its cached snapshot exclusively,
            # so the patch updates the device buffers in place — refresh
            # cost is O(dirty rows), not O(index)
            self._snap = self._snap.apply_delta(patch, donate=True,
                                                layout=self.layout)
        except ValueError:
            return False
        sel = self._rows(patch.rows)
        self._valid = scatter_rows(self.layout, self._valid, sel,
                                   patch.ids >= 0, donate=True)
        self._flat_ids.reshape(self._snap.num_partitions, cap)[sel] = \
            patch.ids
        self._sizes[sel] = patch.sizes
        self._refresh_host_mirrors()   # refine deltas can move centroids
        self._key = self._fingerprint()
        self.delta_refreshes += 1
        return True

    def _rows(self, j, p: Optional[int] = None):
        """Physical snapshot rows of partitions ``j`` (``j`` itself on
        one shard)."""
        return self.layout.rows(
            j, self._snap.num_partitions if p is None else p)

    def _rerank_exact(self, q: np.ndarray, flat: np.ndarray, k: int
                      ) -> Tuple[np.ndarray, np.ndarray]:
        """Exact f32 re-rank of the int8 scan's candidate list: one gather
        of the (B, 2k) candidate rows from the host mirror + exact
        distances, then top-k.  Recovers the quantization-induced rank
        flips near the k-th boundary at negligible extra traffic."""
        b, k2 = flat.shape
        d = self._host_f32.shape[1]
        x = self._host_f32[np.maximum(flat, 0).reshape(-1)]
        x = x.reshape(b, k2, d)
        if self.index.config.metric == "l2":
            diff = x - q[:, None, :]
            de = np.einsum("bkd,bkd->bk", diff, diff, dtype=np.float64)
        else:
            de = -np.einsum("bkd,bd->bk", x, q, dtype=np.float64)
        de = np.where(flat >= 0, de, np.inf)
        order = np.argsort(de, axis=1, kind="stable")[:, :k]
        return (np.take_along_axis(de, order, axis=1),
                np.take_along_axis(flat, order, axis=1))

    def snapshot(self):
        if self._snap is None:
            return self.refresh()
        fp = self._fingerprint()
        if self._key == fp:
            return self._snap
        from .maintenance import fit_to_capacity  # late: import cycle
        fit_to_capacity(self.index, self.headroom)
        delta = self.index.journal.delta_since(self._key[0])
        if delta is None or not self._refresh_delta(delta):
            self.refresh()
        return self._snap

    def search(self, queries: np.ndarray, k: int,
               nprobe: Optional[int] = None,
               recall_target: Optional[float] = None,
               impl: Optional[str] = None,
               union_cap: Optional[int] = None,
               rounds: Optional[int] = None) -> BatchResult:
        q = np.ascontiguousarray(queries, dtype=np.float32)
        if q.ndim == 1:
            q = q[None, :]
        if q.shape[0] == 0:
            return BatchResult(ids=np.zeros((0, k), dtype=np.int64),
                               dists=np.zeros((0, k), dtype=np.float64),
                               nprobe=np.zeros(0, dtype=np.int64),
                               recall_estimate=np.zeros(0))
        snap = self.snapshot()
        rounds = self.rounds if rounds is None else rounds
        if rounds is not None and rounds < 1:
            raise ValueError(f"rounds must be >= 1 or None, got {rounds}")
        cap = self.union_cap if union_cap is None else union_cap
        # early-exit rounds engage only where APS recall machinery exists:
        # nprobe-pinned searches have no per-query estimate to exit on,
        # rounds=1 forces the monolithic fixed-plan scan, the loop
        # planner has no round (seq-aligned) form, and union_cap runs
        # keep the one-shot capped plan (the cap's footprint bound is
        # plan-level; per-round caps would let the batch total exceed it)
        if nprobe is None and rounds != 1 and self.planner != "loop" \
                and not cap:
            target = recall_target if recall_target is not None \
                else self.index.config.recall_target
            return self._search_rounds(q, k, target, rounds, impl=impl,
                                       snap=snap)
        plan = plan_batch(self.index, q, k, nprobe=nprobe,
                          recall_target=recall_target,
                          u_bucket=self.u_bucket,
                          union_cap=self.union_cap if union_cap is None
                          else union_cap,
                          planner=self.planner,
                          cent_norms=self._cent_norms,
                          cache=self.planner_cache)
        # the planner's packed plan is already device-resident; re-upload
        # only if a caller hands in a host-constructed BatchPlan
        sel_dev = plan.sel_dev if plan.sel_dev is not None \
            else jnp.asarray(plan.sel.astype(np.int32))
        qmask_dev = plan.qmask_dev if plan.qmask_dev is not None \
            else jnp.asarray(plan.qmask)
        rerank = (snap.scales is not None and self.int8_rerank
                  and self._host_f32 is not None)
        k_scan = 2 * k if rerank else k
        if self.layout.mapped:
            # the plan as one round: each query takes its packed columns
            kept = plan.sel[:plan.n_real]
            take = np.asarray(plan.qmask[:, :plan.n_real])
            seq = np.broadcast_to(kept.astype(np.int32), take.shape)
            dd, flat, _ = self.scan_probe_round(
                jnp.asarray(q), jnp.asarray(seq), take, kept, k_scan,
                snap=snap, impl=impl)
        elif snap.scales is not None:     # int8 residual codes
            dd, flat = ops.scan_selected_topk_q8(
                jnp.asarray(q), snap.data, snap.scales, self._valid,
                sel_dev, qmask_dev, k_scan,
                metric=self.index.config.metric, centroids=snap.centroids)
        else:
            dd, flat = ops.scan_selected_topk(
                jnp.asarray(q), snap.data, self._valid,
                sel_dev, qmask_dev, k,
                metric=self.index.config.metric, impl=impl or self.impl)
        if rerank:
            # quakecheck: allow-sync(int8 rerank gathers from the host f32 mirror)
            dd, flat = self._rerank_exact(q, np.asarray(flat), k)
        # quakecheck: allow-sync(result boundary: BatchResult is a host contract)
        dd = np.asarray(dd, dtype=np.float64)
        flat = np.asarray(flat)  # quakecheck: allow-sync(result boundary)
        ids = np.where(flat >= 0,
                       self._flat_ids[np.maximum(flat, 0)], -1)
        dd = np.where(dd >= MASK_DIST, np.inf, dd)

        sizes_sel = self._sizes[self._rows(plan.sel[:plan.n_real])]
        return BatchResult(
            ids=ids.astype(np.int64), dists=dd,
            partitions_scanned=int(plan.n_real),
            vectors_scanned=int(sizes_sel.sum()),
            comparisons=int((plan.qmask[:, :plan.n_real].astype(np.int64)
                             * sizes_sel[None, :]).sum()),
            nprobe=plan.nprobe, recall_estimate=plan.recall_est)

    def union_pad(self, n: int, u_pow2: bool = False) -> int:
        """The union width ``scan_probe_round`` pads ``n`` partitions to:
        linear ``u_bucket`` steps, or the geometric ladder with
        ``u_pow2``."""
        n = max(int(n), 1)
        if u_pow2:
            return self.u_bucket * ops._next_pow2(-(-n // self.u_bucket))
        return max(-(-n // self.u_bucket) * self.u_bucket, 1)

    def scan_probe_round(self, q_dev, seq_dev, take: np.ndarray,
                         kept: np.ndarray, k_keep: int, snap=None,
                         impl: Optional[str] = None,
                         u_pow2: bool = False,
                         seq_host: Optional[np.ndarray] = None):
        """One packed partition-union scan for a probe round over an
        arbitrary query row set: ``q_dev`` (B, d) queries, ``seq_dev``
        (B, M) scan-ordered candidate partitions, ``take`` (B, M) bool
        marking the probe-sequence cells consumed this round, ``kept``
        the round's distinct union partition ids.  Packs through
        ``ops.pack_round_masked`` (bucketed union width, inert tail
        applied on device) and scans the snapshot once; returns device
        ``(dists (B, k_keep), flat idx (B, k_keep), stats)`` in
        ``run_round_loop``'s ``scan_round`` contract.

        This is the scan primitive both round drivers share: the
        fixed-membership per-batch loop (``_search_rounds``) and the
        serving scheduler's cross-batch riding rounds
        (``core/serving.py``), where the active row set changes between
        rounds as queued batches join mid-flight.  ``u_pow2`` switches
        the union padding from linear ``u_bucket`` steps to a geometric
        ladder (``u_bucket * 2^i``) — serving rounds see wildly varying
        union sizes, and the ladder bounds the distinct compiled scan
        shapes at log cost instead of linear.

        ``seq_host`` is the host mirror of ``seq_dev``: with it the
        per-round comparison count is exact (every taken cell weighted
        by its partition size — candidate partitions are distinct within
        a row, so this equals the packed qmask accounting) without
        pulling the packed plan off device; without it the stats report
        ``comparisons == vectors`` (each streamed partition counted
        once).
        """
        snap = self.snapshot() if snap is None else snap
        kept = np.asarray(kept, dtype=np.int64)
        p_snap = int(snap.num_partitions)
        # stats from the host-side plan data the caller already holds —
        # the packed plan itself never leaves the device
        sizes_kept = self._sizes[self._rows(kept, p_snap)]
        vectors = int(sizes_kept.sum())
        if seq_host is not None:
            comparisons = int(self._sizes[self._rows(seq_host[take],
                                                     p_snap)].sum())
        else:
            comparisons = vectors
        u_pad, n_real = self.round_width(kept, u_pow2)
        st = {"partitions": int(max(len(kept), 1)), "vectors": vectors,
              "comparisons": comparisons, "shard_vectors_max": vectors}
        if self.layout.mapped:
            shard = kept % self.shards
            st["shard_vectors_max"] = int(np.bincount(
                shard, weights=sizes_kept, minlength=self.shards).max())
            per_shard = np.bincount(shard, minlength=self.shards)
            q8 = snap.scales is not None
            take = np.asarray(take)
            b = take.shape[0]
            pad = -b % self.layout.batch_shards   # rows split evenly
            if pad:
                q_dev = jnp.pad(q_dev, ((0, pad), (0, 0)))
                seq_dev = jnp.pad(seq_dev, ((0, pad), (0, 0)))
                take = np.pad(take, ((0, pad), (0, 0)))
            args = (q_dev, seq_dev, jnp.asarray(take),
                    self.layout.put(per_shard.astype(np.int32)),
                    snap.data, self._valid)
            if q8:
                args += (snap.scales, snap.centroids)
            d, flat = self._sharded_round(u_pad, k_keep, impl or self.impl,
                                          q8)(*args)
            if pad:
                d, flat = d[:b], flat[:b]
            return d, flat, st
        # pack against the snapshot's (padded) partition count: stable
        # across rebuilds when part_bucket > 1, so the jitted pack
        # survives maintenance epochs
        p = max(self.index.levels[0].num_partitions, p_snap)
        prio0 = jnp.zeros((p,), jnp.int32)   # uncapped: no anchor boost
        # pack + inert-tail masking on device (no host round trip; the
        # dynamic n_real scalar shares one executable across round sizes)
        sel_dev, qmask_dev = ops.pack_round_masked(
            seq_dev, jnp.asarray(take), prio0, max(n_real, 1), p=p,
            u_pad=u_pad)
        if snap.scales is not None:
            d, flat = ops.scan_selected_topk_q8(
                q_dev, snap.data, snap.scales, self._valid,
                sel_dev, qmask_dev, k_keep,
                metric=self.index.config.metric, centroids=snap.centroids)
        else:
            d, flat = ops.scan_selected_topk(
                q_dev, snap.data, self._valid, sel_dev, qmask_dev,
                k_keep, metric=self.index.config.metric,
                impl=impl or self.impl)
            self.last_scan = {
                "operands": [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in
                             (q_dev, snap.data, self._valid, sel_dev,
                              qmask_dev)],
                "k": k_keep, "metric": self.index.config.metric,
                "impl": ops._resolve(impl or self.impl)}
        return d, flat, st

    def round_width(self, kept, u_pow2: bool = False) -> Tuple[int, int]:
        """What a round over the union partitions ``kept`` scans on each
        shard: (its padded union width, the largest shard's count of
        ``kept``).  One shard: the whole union."""
        if self.shards == 1:
            n = len(kept)
        else:
            n = int(np.bincount(np.asarray(kept, dtype=np.int64)
                                % self.shards,
                                minlength=self.shards).max())
        return self.union_pad(n, u_pow2), n

    def _sharded_round(self, u_pad: int, k: int, impl: str, q8: bool):
        """The jitted round over a sharded snapshot: one ``shard_map`` in
        which every shard masks the round's cells to its own partitions
        (``j % shards``), packs them over its local rows (``j //
        shards``) at the static width ``u_pad`` with its own live count,
        scans them with the one-chip kernel, offsets its flat indices to
        physical rows and merges the shards' top-``k`` lists over the
        mesh.  A layout with a ``batch_axis`` splits the query rows over
        it (each replica of the partition shards takes its share of the
        batch).  Cached per static shape (the snapshot's rows reshape it
        through jit's own cache)."""
        key = (u_pad, k, impl, q8)
        fn = self._round_fns.get(key)
        if fn is not None:
            return fn
        lay = self.layout
        n = lay.shards
        metric = self.index.config.metric

        def local(q, seq, take, n_real, data, valid, *q8_ops):
            i = jax.lax.axis_index(lay.axes)
            p_loc, s_cap = data.shape[0], data.shape[1]
            sel, qmask = ops.pack_round_masked(
                seq // n, take & (seq % n == i),
                jnp.zeros((p_loc,), jnp.int32), n_real[0], p=p_loc,
                u_pad=u_pad)
            if q8_ops:
                scales, cents = q8_ops
                d, flat = ops.scan_selected_topk_q8(
                    q, data, scales, valid, sel, qmask, k, metric=metric,
                    centroids=cents)
            else:
                d, flat = ops.scan_selected_topk(
                    q, data, valid, sel, qmask, k, metric=metric, impl=impl)
            flat = jnp.where(flat >= 0, flat + i * (p_loc * s_cap), -1)
            return merge_shards(d, flat, k, lay.axes)

        part = P(lay.axes)
        rows = P(lay.batch_axis) if lay.batch_shards > 1 else P()
        fn = jax.jit(shard_map(
            local, mesh=lay.mesh,
            in_specs=(rows, rows, rows, part, part, part)
            + ((part, part) if q8 else ()),
            out_specs=(rows, rows), check_vma=False))
        self._round_fns[key] = fn
        return fn

    def _search_rounds(self, q: np.ndarray, k: int, target: float,
                       rounds: Optional[int],
                       impl: Optional[str] = None,
                       snap=None) -> BatchResult:
        """Multi-round early-exit search (Algorithm 2 semantics): the
        planned probe sequences are chunked into geometrically growing
        rounds; each round packs only *live* queries' next probes
        (``ops.pack_round``), scans them once
        (``scan_selected_topk``/``_q8``), folds the result into a
        device-resident running top-k, and the shared round driver
        re-estimates per-query recall from the running k-th distance —
        queries that clear the target stop paying for further rounds."""
        idx = self.index
        snap = self.snapshot() if snap is None else snap
        rplan = plan_rounds(idx, q, k, target, planner=self.planner,
                            cache=self.planner_cache,
                            cent_norms=self._cent_norms)
        q_dev = jnp.asarray(q)
        seq_dev = rplan.seq_dev if rplan.seq_dev is not None \
            else jnp.asarray(rplan.seq.astype(np.int32))
        rerank = (snap.scales is not None and self.int8_rerank
                  and self._host_f32 is not None)
        k_keep = 2 * k if rerank else k

        def scan_round(take, kept):
            return self.scan_probe_round(q_dev, seq_dev, take, kept,
                                         k_keep, snap=snap, impl=impl,
                                         seq_host=rplan.seq)

        td, ti, nprobe, r_est, n_rounds, trace, stats = run_round_loop(
            rplan, k, target, idx._beta_table, _batch_rho_fn(idx, q),
            scan_round, rounds=rounds, k_keep=k_keep)
        if rerank:
            # quakecheck: allow-sync(int8 rerank gathers from the host f32 mirror)
            dd, flat = self._rerank_exact(q, np.asarray(ti), k)
        else:
            # quakecheck: allow-sync(result boundary: BatchResult is a host contract)
            dd = np.asarray(td, dtype=np.float64)[:, :k]
            flat = np.asarray(ti)[:, :k]  # quakecheck: allow-sync(result boundary)
        ids = np.where(flat >= 0,
                       self._flat_ids[np.maximum(flat, 0)], -1)
        dd = np.where(dd >= MASK_DIST, np.inf, dd)
        return BatchResult(
            ids=ids.astype(np.int64), dists=dd,
            partitions_scanned=stats["partitions"],
            vectors_scanned=stats["vectors"],
            comparisons=stats["comparisons"],
            nprobe=nprobe, recall_estimate=r_est,
            rounds=n_rounds, round_trace=trace)


def get_executor(index: QuakeIndex,
                 storage_dtype: Optional[str] = None
                 ) -> BatchedSearchExecutor:
    """The index's cached executor for ``storage_dtype`` (snapshot reuse
    across calls; one executor — and one device snapshot — per storage
    format).  ``None`` means the default f32 executor."""
    key = storage_dtype or "f32"
    cache = getattr(index, "_batch_executors", None)
    if cache is None:
        cache = index._batch_executors = {}
    ex = cache.get(key)
    if ex is None or ex.index is not index:
        # identity guard: a transplanted __dict__ (copy/pickle) carries
        # the cache but its executors still point at the source index
        ex = BatchedSearchExecutor(index, storage_dtype=key)
        cache[key] = ex
    return ex


def batch_search(index: QuakeIndex, queries: np.ndarray, k: int,
                 nprobe: Optional[int] = None,
                 recall_target: Optional[float] = None,
                 impl: str = "auto",
                 union_cap: Optional[int] = None,
                 storage_dtype: Optional[str] = None,
                 rounds: Optional[int] = None) -> BatchResult:
    """Scan-each-partition-once batched search over the dynamic index.

    Partition selection per query uses centroid order with a fixed
    ``nprobe`` (the policy in the paper's Fig. 5 experiment), or, when
    ``nprobe`` is None, APS-driven per-query probe counts (see
    ``plan_batch``) executed as multi-round early-exit probe rounds
    (Algorithm 2; ``rounds=1`` forces the monolithic fixed-plan scan).
    The scan itself is device-resident packed union scans;
    ``storage_dtype`` picks the f32/bf16/int8 snapshot format and
    ``union_cap`` bounds the scanned union under read skew (plan-level
    truncation — capped searches take the one-shot fixed plan).
    """
    return get_executor(index, storage_dtype).search(
        queries, k, nprobe=nprobe, recall_target=recall_target, impl=impl,
        union_cap=union_cap, rounds=rounds)


def per_query_search(index: QuakeIndex, queries: np.ndarray, k: int,
                     nprobe: Optional[int] = None,
                     recall_target: Optional[float] = None,
                     impl: str = "auto") -> BatchResult:
    """Baseline: one-at-a-time search — the B=1 case of the same executor,
    so partitions are re-scanned per query (Faiss-IVF behaviour) but the
    code path and kernels are identical to the batched policy, including
    the APS planner when ``recall_target`` drives probe counts."""
    q = np.ascontiguousarray(queries, dtype=np.float32)
    if q.shape[0] == 0:
        return BatchResult(ids=np.zeros((0, k), dtype=np.int64),
                           dists=np.zeros((0, k), dtype=np.float64),
                           nprobe=np.zeros(0, dtype=np.int64))
    ex = get_executor(index)
    ids, dists, parts, vecs, comps = [], [], 0, 0, 0
    nps, rests, max_rounds = [], [], 1
    for row in q:
        r = ex.search(row[None, :], k, nprobe=nprobe,
                      recall_target=recall_target, impl=impl)
        ids.append(r.ids[0])
        dists.append(r.dists[0])
        parts += r.partitions_scanned
        vecs += r.vectors_scanned
        comps += r.comparisons
        nps.append(int(r.nprobe[0]) if r.nprobe is not None else 0)
        rests.append(float(r.recall_estimate[0])
                     if r.recall_estimate is not None else np.nan)
        max_rounds = max(max_rounds, r.rounds)
    rest = np.asarray(rests)
    return BatchResult(ids=np.stack(ids), dists=np.stack(dists),
                       partitions_scanned=parts, vectors_scanned=vecs,
                       comparisons=comps, nprobe=np.asarray(nps),
                       recall_estimate=None if np.isnan(rest).all()
                       else rest, rounds=max_rounds)

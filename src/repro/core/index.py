"""Quake's multi-level partitioned index (paper §3) — the dynamic engine.

The partition directory (ragged inverted lists, id maps, statistics) is a
host-side control plane; scans run through a pluggable backend:

  * ``numpy``  — BLAS matmul + argpartition; the fast path for the online
                 engine on CPU (per-partition scans are tiny and jax dispatch
                 overhead would dominate).
  * ``jnp``    — jitted oracle path (XLA), used for validation.
  * ``pallas`` — the fused TPU kernel in interpret mode on CPU / Mosaic on
                 TPU.

Level structure: level 0 partitions hold data vectors; level ``l`` partitions
group the *centroids* of level ``l-1`` (paper: "These centroids can be
further partitioned ... to create additional levels").  Search walks
top-down, running APS at every level; the items returned by APS at level
``l>0`` are exactly the candidate partitions (plus centroid distances) for
level ``l-1``.

The compiled, mesh-sharded engine (``distributed.ShardedQuakeEngine``) consumes
snapshots of this structure.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from ..kernels import ops
from . import aps as aps_mod
from . import geometry, kmeans
from .cost_model import LatencyModel, PartitionStats
from .journal import MutationJournal

__all__ = ["QuakeConfig", "QuakeIndex", "Level", "SearchResult"]


@dataclass
class QuakeConfig:
    metric: str = "l2"                  # "l2" | "ip"
    f_m: float = 0.05                   # base-level initial candidate fraction
    f_m_upper: float = 0.25             # candidate fraction at upper levels
    min_candidates: int = 32            # floor on the APS candidate set; f_M
                                        # percentages are tuned for >=1000
                                        # partitions (paper SIFT1M) and starve
                                        # the estimator on small indexes
    recall_target: float = 0.9
    recall_target_upper: float = 0.99   # fixed for higher levels (paper §5.1)
    tau_rho: float = 0.01               # radius recompute threshold
    scan_impl: str = "numpy"            # numpy | jnp | pallas
    enable_aps: bool = True             # ablation: static nprobe when False
    fixed_nprobe: int = 16              # used when enable_aps=False
    # --- maintenance (paper §8.1 defaults, rescaled to our lambda) ---
    # The paper sets tau = 250ns against a profile where lambda(500) =
    # 1.2e6 ns (their Xeon, d>=100, k=100 scans).  Our profiled lambda(500)
    # is ~2e3 ns (numpy, d=32), so the equivalent threshold is
    # 250 * (2e3 / 1.2e6) ~= 0.4 ns.  We default to 2 ns — the same
    # "tiny fraction of one partition-scan" semantics as the paper.
    tau_ns: float = 2.0                 # commit threshold tau
    alpha: float = 0.9                  # split access-scaling
    refine_radius: int = 50             # r_f
    refine_iters: int = 1
    min_partition_size: int = 32        # merge candidates below this size
    default_access_freq: float = 0.05   # prior before stats exist
    # --- levels ---
    level_add_threshold: int = 4096     # add top level when N_top exceeds
    level_remove_threshold: int = 64    # drop top level when N_top below
    # --- snapshot refresh (COW delta path, paper §8.2) ---
    snapshot_headroom: float = 1.5      # slack factor on snapshot slot
                                        # capacity so insert deltas rarely
                                        # force a full reshape/rebuild
    snapshot_capacity: Optional[int] = None  # fixed slot capacity of the
                                        # device snapshot, for one that must
                                        # stay within device memory: a
                                        # partition that outgrows it is
                                        # split to capacity / headroom
                                        # (maintenance.split_to_fit).  None:
                                        # slots follow the largest partition
    snapshot_max_dirty_frac: float = 0.5  # delta-refresh only while dirty
                                        # partitions <= frac * P; beyond
                                        # that a full rebuild is cheaper
    # --- batched executor (multiquery.py) ---
    union_cap: Optional[int] = None     # max distinct partitions one batch
                                        # scans (frequency-ranked truncation
                                        # under read skew; None = unbounded)
                                        # — the batched-executor mirror of
                                        # EngineConfig.union_cap
    planner_radius_ttl: int = 64        # batches a calibrated APS radius may
                                        # be reused for before the planner
                                        # cache recalibrates (bounds query-
                                        # distribution-drift staleness; see
                                        # multiquery.PlannerCache)
    seed: int = 0


@dataclass
class Level:
    """One level of the hierarchy.  Exactly one of (vectors, children) is
    populated: level 0 stores data vectors, upper levels store child
    partition-index lists."""
    centroids: np.ndarray                       # (P, d) float32
    vectors: Optional[List[np.ndarray]] = None  # level 0: (s_j, d) each
    ids: Optional[List[np.ndarray]] = None      # level 0: external ids
    sqnorms: Optional[List[np.ndarray]] = None  # level 0: cached ||x||^2
    children: Optional[List[np.ndarray]] = None  # level>0: level-1 part idx
    parent: Optional[np.ndarray] = None         # partition idx at level+1
    stats: PartitionStats = field(default_factory=PartitionStats)

    @property
    def num_partitions(self) -> int:
        return self.centroids.shape[0]

    def partition_size(self, j: int) -> int:
        if self.vectors is not None:
            return len(self.vectors[j])
        return len(self.children[j])

    def sizes(self) -> np.ndarray:
        n = self.num_partitions
        if self.vectors is not None:
            return np.asarray([len(self.vectors[j]) for j in range(n)])
        return np.asarray([len(self.children[j]) for j in range(n)])

    def sizes_of(self, idx) -> np.ndarray:
        """Sizes of just the given partitions — the per-round
        calibration hook uses this instead of ``sizes()[idx]`` so the
        cost scales with the scanned set, not the level width."""
        store = self.vectors if self.vectors is not None else self.children
        return np.asarray([len(store[j]) for j in np.asarray(idx).ravel()])


@dataclass
class SearchResult:
    ids: np.ndarray
    dists: np.ndarray          # minimization convention (-score for ip)
    nprobe: Dict[int, int]     # partitions scanned per level
    recall_estimate: float
    vectors_scanned: int = 0

    @property
    def scores(self) -> np.ndarray:
        return -self.dists


class QuakeIndex:
    """Dynamic multi-level partitioned ANN index with APS search."""

    def __init__(self, dim: int, config: Optional[QuakeConfig] = None):
        self.dim = dim
        self.config = config or QuakeConfig()
        self.levels: List[Level] = []
        self.id_map: Dict[int, int] = {}     # external id -> level-0 partition
        self.journal = MutationJournal()     # per-partition dirty sets +
                                             # structural flags; snapshot
                                             # caches consume deltas from it
        self._rng = np.random.default_rng(self.config.seed)
        # APS's cap model: the dimension of its geometry and the fraction
        # f_M of nearest partitions it considers.  They start at the
        # paper's (the data's dimension, config.f_m); calibrate_aps fits
        # them to the data
        self.model_dim = dim if self.config.metric == "l2" else dim + 1
        self.geometry_dim = self.model_dim
        self._beta_table = geometry.betainc_table(self.geometry_dim)
        self.aps_f_m = self.config.f_m
        self.aps_calibration: dict = {}
        self._max_norm_sq = 1e-12           # MIPS augmentation constant M^2
        self._aug_extra: List[Optional[np.ndarray]] = []  # per level cache
        self.maintenance_log: List[dict] = []

    # ------------------------------------------------------------------
    # Construction
    # ------------------------------------------------------------------

    @classmethod
    def build(cls, x: np.ndarray, ids: Optional[np.ndarray] = None,
              num_partitions: Optional[int] = None,
              level_sizes: Optional[Sequence[int]] = None,
              config: Optional[QuakeConfig] = None,
              kmeans_iters: int = 10) -> "QuakeIndex":
        """Build from data.  ``num_partitions`` defaults to sqrt(n) (paper
        §7.2).  ``level_sizes`` optionally gives partition counts for upper
        levels, e.g. (40000, 500) for the two-level SIFT10M setup."""
        x = np.ascontiguousarray(x, dtype=np.float32)
        n, dim = x.shape
        idx = cls(dim, config)
        if ids is None:
            ids = np.arange(n, dtype=np.int64)
        if level_sizes is None:
            p0 = num_partitions or max(1, int(round(math.sqrt(n))))
            level_sizes = (p0,)
        # the largest squared norm, in row blocks (no float64 copy of x)
        blk = 1 << 16
        idx._max_norm_sq = max(max((float(np.max(np.sum(
            x[i:i + blk].astype(np.float64) ** 2, axis=1), initial=0.0))
            for i in range(0, n, blk)), default=0.0), 1e-12)

        # level 0: the rows grouped by partition in one stable sort, so
        # each partition keeps the rows in their order
        p0 = min(level_sizes[0], n)
        cents, assign = kmeans.kmeans(x, p0, iters=kmeans_iters,
                                      seed=idx.config.seed)
        order = np.argsort(assign, kind="stable")
        bounds = np.searchsorted(assign[order], np.arange(p0 + 1))
        xs, ids_s = x[order], ids[order].astype(np.int64)
        vectors = [xs[a:b] for a, b in zip(bounds[:-1], bounds[1:])]
        vids = [ids_s[a:b] for a, b in zip(bounds[:-1], bounds[1:])]
        lvl0 = Level(centroids=cents, vectors=vectors, ids=vids,
                     sqnorms=[np.sum(v.astype(np.float64) ** 2, axis=1)
                              .astype(np.float32) for v in vectors])
        idx.levels.append(lvl0)
        idx.id_map.update(zip(ids.tolist(), assign.tolist()))

        # upper levels: cluster the centroids of the level below
        for p_l in level_sizes[1:]:
            idx._add_level_from(p_l, kmeans_iters)
        idx._aug_extra = [None] * len(idx.levels)
        idx.calibrate_aps()
        return idx

    def _add_level_from(self, p_l: int, iters: int = 10) -> None:
        below = self.levels[-1]
        cents_below = below.centroids
        p_l = min(p_l, cents_below.shape[0])
        cents, assign = kmeans.kmeans(cents_below, p_l, iters=iters,
                                      seed=self.config.seed + len(self.levels))
        children = [np.where(assign == j)[0].astype(np.int64)
                    for j in range(p_l)]
        below.parent = assign.astype(np.int64)
        self.levels.append(Level(centroids=cents, children=children))
        self._aug_extra = [None] * len(self.levels)
        # upper levels are not part of the base-level snapshot: bump the
        # clock (planning structures changed) but dirty nothing
        self.journal.record(reason="level_add")

    def remove_top_level(self) -> None:
        """Drop the top level (paper §4.2.1 Remove Level): the level below is
        then scanned fully at query time."""
        assert len(self.levels) >= 2
        self.levels.pop()
        self.levels[-1].parent = None
        self._aug_extra = [None] * len(self.levels)
        self.journal.record(reason="level_remove")

    # ------------------------------------------------------------------
    # Metric helpers
    # ------------------------------------------------------------------

    def _centroid_geo_dists(self, q: np.ndarray, level_idx: int,
                            part_ids: np.ndarray
                            ) -> Tuple[np.ndarray, np.ndarray]:
        """Returns (geometry-space squared distances (M,), scan-order keys).

        For L2 both are ||q-c||^2.  For IP the geometry distances live in the
        MIPS-augmented space (||q||^2 + M^2 - 2 s) while the scan keys are
        -s; both orders coincide.
        """
        c = self.levels[level_idx].centroids[part_ids]
        if self.config.metric == "l2":
            d = (np.sum(q * q) + np.sum(c * c, axis=1) - 2.0 * (c @ q))
            d = np.maximum(d, 0.0)
            return d, d
        s = c @ q
        geo = np.maximum(np.sum(q * q) + self._max_norm_sq - 2.0 * s, 0.0)
        return geo, -s

    def _centroid_cc_dists(self, level_idx: int, part_ids: np.ndarray,
                           nearest_local: int) -> np.ndarray:
        """||c_i - c_0|| in geometry space (augmented for IP)."""
        c = self.levels[level_idx].centroids[part_ids].astype(np.float64)
        c0 = c[nearest_local]
        d2 = np.sum((c - c0) ** 2, axis=1)
        if self.config.metric == "ip":
            e = self._augment_extra(level_idx)[part_ids]
            d2 = d2 + (e - e[nearest_local]) ** 2
        return np.sqrt(np.maximum(d2, 0.0))

    def _augment_extra(self, level_idx: int) -> np.ndarray:
        cached = self._aug_extra[level_idx]
        c = self.levels[level_idx].centroids
        if cached is None or len(cached) != c.shape[0]:
            n2 = np.sum(c.astype(np.float64) ** 2, axis=1)
            m2 = self._max_norm_sq
            cached = np.sqrt(np.maximum(m2 - n2, 0.0))
            self._aug_extra[level_idx] = cached
        return cached

    def _rho_sq_from_item_dist(self, q_norm_sq: float):
        if self.config.metric == "l2":
            return lambda kth: max(kth, 0.0)
        m2 = self._max_norm_sq
        # item dist = -score  ->  rho^2 = ||q||^2 + M^2 - 2 score
        return lambda kth: max(q_norm_sq + m2 + 2.0 * kth, 0.0)

    # ------------------------------------------------------------------
    # Scanning backends
    # ------------------------------------------------------------------

    def _scan_vectors(self, q: np.ndarray, x: np.ndarray,
                      x2: Optional[np.ndarray], item_ids: np.ndarray,
                      k: int) -> Tuple[np.ndarray, np.ndarray]:
        """Scan a ragged buffer; returns (dists, ids) of its top-min(k, s)."""
        impl = self.config.scan_impl
        if impl == "numpy":
            if self.config.metric == "l2":
                if x2 is None:
                    x2 = np.sum(x * x, axis=1)
                d = x2 - 2.0 * (x @ q) + np.sum(q * q)
            else:
                d = -(x @ q)
            if len(d) > k:
                sel = np.argpartition(d, k - 1)[:k]
                return d[sel], item_ids[sel]
            return d, item_ids
        dd, ii = ops.scan_topk(jnp.asarray(q[None, :]), jnp.asarray(x),
                               min(k, x.shape[0]), metric=self.config.metric,
                               impl=impl)
        dd = np.asarray(dd[0])
        ii = np.asarray(ii[0])
        keep = ii >= 0
        return dd[keep], item_ids[ii[keep]]

    def _scan_level_partition(self, q: np.ndarray, level_idx: int, j: int,
                              k: int) -> Tuple[np.ndarray, np.ndarray]:
        level = self.levels[level_idx]
        if level.vectors is not None:
            return self._scan_vectors(q, level.vectors[j], level.sqnorms[j],
                                      level.ids[j], k)
        child = level.children[j]
        below = self.levels[level_idx - 1]
        return self._scan_vectors(q, below.centroids[child], None, child, k)

    # ------------------------------------------------------------------
    # Search (paper §5)
    # ------------------------------------------------------------------

    def search(self, q: np.ndarray, k: int,
               recall_target: Optional[float] = None,
               nprobe: Optional[int] = None,
               record_stats: bool = True) -> SearchResult:
        """APS search.  ``nprobe`` (or config.enable_aps=False) switches to a
        fixed number of probes at the base level — the static baseline."""
        q = np.ascontiguousarray(q, dtype=np.float32).reshape(-1)
        cfg = self.config
        target = recall_target if recall_target is not None else \
            cfg.recall_target
        q_norm_sq = float(np.sum(q.astype(np.float64) ** 2))
        rho_fn = self._rho_sq_from_item_dist(q_norm_sq)

        L = len(self.levels)
        top = self.levels[-1]
        cand = np.arange(top.num_partitions)
        cand_geo, _ = self._centroid_geo_dists(q, L - 1, cand)
        nprobe_per_level: Dict[int, int] = {}
        vectors_scanned = 0
        recall_est = 1.0

        for l in range(L - 1, -1, -1):
            level = self.levels[l]
            if l == 0:
                k_l, tgt, f_m = k, target, self.aps_f_m
            else:
                below_n = self.levels[l - 1].num_partitions
                f_m_below = self.aps_f_m if l - 1 == 0 else cfg.f_m_upper
                # APS at level l must find, with high recall, the candidates
                # the level below will consider:
                k_l = max(k, int(math.ceil(f_m_below * below_n)))
                tgt, f_m = cfg.recall_target_upper, cfg.f_m_upper
            n_consider = max(int(math.ceil(f_m * level.num_partitions)),
                             cfg.min_candidates)
            use_aps = cfg.enable_aps and nprobe is None
            if not use_aps and l == 0:
                # fixed-nprobe baselines scan exactly nprobe partitions; the
                # f_M candidate restriction only applies to APS
                n_consider = max(n_consider,
                                 nprobe if nprobe is not None
                                 else cfg.fixed_nprobe)
            n_consider = min(max(n_consider, 1), len(cand))
            # restrict to the n_consider nearest candidates
            if n_consider < len(cand):
                sel = np.argpartition(cand_geo, n_consider - 1)[:n_consider]
                cand, cand_geo = cand[sel], cand_geo[sel]
            nearest_local = int(np.argmin(cand_geo))
            cc = self._centroid_cc_dists(l, cand, nearest_local)

            sizes = level.sizes()
            scanned_count = [0]

            def scan_fn(m: int, _l=l, _cand=cand, _k=k_l, _sc=scanned_count):
                _sc[0] += int(sizes[_cand[m]])
                return self._scan_level_partition(q, _l, int(_cand[m]), _k)

            if use_aps:
                res = aps_mod.aps_scan(
                    cand_centroid_dists_sq=cand_geo,
                    cand_cc_dists=cc,
                    scan_partition=scan_fn,
                    item_dist_to_rho_sq=rho_fn,
                    k=k_l, recall_target=tgt, table=self._beta_table,
                    tau_rho=cfg.tau_rho)
            else:
                n_fixed = nprobe if nprobe is not None else cfg.fixed_nprobe
                res = self._fixed_scan(cand_geo, scan_fn, k_l,
                                       min(n_fixed, len(cand)))
            vectors_scanned += scanned_count[0]
            nprobe_per_level[l] = res.nprobe
            if record_stats:
                level.stats.ensure(level.num_partitions)
                level.stats.record(cand[res.scanned])
            if l == 0:
                recall_est = res.recall_estimate
                keep = res.ids >= 0
                return SearchResult(ids=res.ids[keep],
                                    dists=res.dists[keep],
                                    nprobe=nprobe_per_level,
                                    recall_estimate=recall_est,
                                    vectors_scanned=vectors_scanned)
            # descend: top items are level l-1 partition ids
            keep = res.ids >= 0
            cand = res.ids[keep].astype(np.int64)
            # geometry distances for the next level from the item distances
            if cfg.metric == "l2":
                cand_geo = np.maximum(res.dists[keep], 0.0)
            else:
                cand_geo = np.maximum(
                    q_norm_sq + self._max_norm_sq + 2.0 * res.dists[keep],
                    0.0)
            if len(cand) == 0:  # degenerate hierarchy: fall back to full set
                cand = np.arange(self.levels[l - 1].num_partitions)
                cand_geo, _ = self._centroid_geo_dists(q, l - 1, cand)
        raise AssertionError("unreachable")

    def search_batch(self, queries: np.ndarray, k: int,
                     nprobe: Optional[int] = None,
                     recall_target: Optional[float] = None,
                     impl: str = "auto",
                     union_cap: Optional[int] = None,
                     storage_dtype: Optional[str] = None,
                     rounds: Optional[int] = None):
        """Batched multi-query search (paper §7.4) through the
        device-resident executor: per-query probe sets are planned by the
        vectorized batch planner (APS-driven when ``nprobe`` is None) and
        executed as multi-round early-exit probe rounds (paper
        Algorithm 2): each round scans one packed partition union via the
        ``scan_topk_indexed`` kernel and queries whose refined recall
        estimate clears the target drop out of later rounds.  ``rounds``
        bounds the round budget (1 = single fixed-plan scan; also the
        shape nprobe-pinned searches always take).  ``union_cap`` bounds
        each scanned union (frequency-ranked, for read-skewed batches);
        ``storage_dtype`` ("f32"/"bf16"/"int8") selects the snapshot
        storage format.  Single-query search is the B=1 case of the same
        path.  Returns ``multiquery.BatchResult`` — APS-planned results
        carry per-query ``recall_estimate``s like the per-query path.
        """
        from .multiquery import batch_search  # late: avoid import cycle
        return batch_search(self, queries, k, nprobe=nprobe,
                            recall_target=recall_target, impl=impl,
                            union_cap=union_cap,
                            storage_dtype=storage_dtype, rounds=rounds)

    @staticmethod
    def _fixed_scan(cand_geo, scan_fn, k, n_fixed) -> aps_mod.APSResult:
        order = np.argsort(cand_geo, kind="stable")[:max(n_fixed, 1)]
        heap = aps_mod.TopK(k)
        for m in order:
            d, i = scan_fn(int(m))
            heap.update(d, i)
        return aps_mod.APSResult(ids=heap.ids, dists=heap.dists,
                                 scanned=np.asarray(order),
                                 nprobe=len(order), recall_estimate=np.nan)

    # ------------------------------------------------------------------
    # APS calibration
    # ------------------------------------------------------------------

    _CALIB_QUERIES = 64    # leave-one-out sample of resident vectors
    _CALIB_K = 10          # neighbours the calibration counts
    _CALIB_DIMS = (256, 64, 16, 4)
    _CALIB_RUN = 8192      # candidate columns sorted at once

    def set_aps_model(self, f_m: float, geometry_dim: int) -> None:
        """Set APS's candidate fraction and cap dimension (what
        ``calibrate_aps`` fits; checkpoints restore it)."""
        self.aps_f_m = float(f_m)
        self.geometry_dim = int(geometry_dim)
        self._beta_table = geometry.betainc_table(self.geometry_dim)

    def calibrate_aps(self) -> dict:
        """Fit APS's cap model to this index's data.

        The model rates a candidate partition by the share of the query
        ball beyond its bisector, in ``geometry_dim`` dimensions, over the
        ``f_M * P`` nearest partitions.  Where a query's neighbours spread
        over many partitions (topics of isotropic noise at d=768) both
        starve APS: the share falls off as ``(1 - (h/rho)^2)^(d/2)``, the
        estimate passes the target after a few probes, and the recall is
        far below it.  Calibration takes ``_CALIB_QUERIES`` resident
        vectors as leave-one-out queries, finds their exact ``_CALIB_K``
        nearest neighbours, and keeps the first
        setting whose mean recall meets ``recall_target``: the unfitted
        model, else f_M doubling towards 1 and, for the first f_M that
        can reach the target, the cap dimension falling from the data's
        towards 4.  Returns (and keeps in ``aps_calibration``) what it
        measured; an index too small to sample keeps the unfitted model.
        """
        cfg = self.config
        nq, k = self._CALIB_QUERIES, self._CALIB_K
        lvl0 = self.levels[0]
        sizes = lvl0.sizes()
        n = int(sizes.sum())
        if n <= nq + k or not cfg.enable_aps:
            return {}
        rng = np.random.default_rng(cfg.seed)
        flat = np.sort(rng.choice(n, nq, replace=False))
        starts = np.cumsum(sizes) - sizes
        part = np.searchsorted(starts, flat, side="right") - 1
        q = np.stack([lvl0.vectors[p][f - starts[p]]
                      for p, f in zip(part, flat)])
        self_ids = np.asarray([lvl0.ids[p][f - starts[p]]
                               for p, f in zip(part, flat)])
        # exact top-(k+1) over every partition, streamed in runs of
        # partitions: a stable sort keeps ties in partition order
        best_d = np.full((nq, 0), np.inf)
        best_i = np.full((nq, 0), -1, dtype=np.int64)
        run_d, run_i, run_n = [best_d], [best_i], 0
        for j in range(lvl0.num_partitions + 1):
            if j < lvl0.num_partitions:
                if not sizes[j]:
                    continue
                d = -(q @ lvl0.vectors[j].T).astype(np.float64)
                if cfg.metric == "l2":
                    d = 2.0 * d + lvl0.sqnorms[j][None, :]
                run_d.append(d)
                run_i.append(np.broadcast_to(lvl0.ids[j], d.shape))
                run_n += d.shape[1]
                if run_n < self._CALIB_RUN:
                    continue
            d = np.concatenate(run_d, axis=1)
            i = np.concatenate(run_i, axis=1)
            keep = np.argsort(d, axis=1, kind="stable")[:, :k + 1]
            best_d = np.take_along_axis(d, keep, axis=1)
            best_i = np.take_along_axis(i, keep, axis=1)
            run_d, run_i, run_n = [best_d], [best_i], 0
        truth = [set([i for i in row if i != s][:k])
                 for row, s in zip(best_i.tolist(), self_ids.tolist())]

        before = (self.aps_f_m, self.geometry_dim)
        tried = []

        def recall_at(f_m: float, gdim: int) -> float:
            self.set_aps_model(f_m, gdim)
            hits = 0
            for x, s, t in zip(q, self_ids.tolist(), truth):
                r = self.search(x, k + 1, record_stats=False)
                got = [i for i in r.ids.tolist() if i != s][:k]
                hits += len(t.intersection(got))
            rec = hits / (k * nq)
            tried.append((f_m, gdim, rec))
            return rec

        target = cfg.recall_target
        dims = [self.model_dim] + [g for g in self._CALIB_DIMS
                                   if 2 * g <= self.model_dim]
        # f_M doubling towards 1, each step adding candidates
        p0 = lvl0.num_partitions
        f_ladder, f, seen = [], cfg.f_m, set()
        while True:
            f = min(f, 1.0)
            n_cand = min(max(math.ceil(f * p0), cfg.min_candidates), p0)
            if n_cand not in seen:
                seen.add(n_cand)
                f_ladder.append(f)
            if f >= 1.0:
                break
            f *= 2.0
        chosen = None
        if recall_at(cfg.f_m, dims[0]) >= target:
            chosen = (cfg.f_m, dims[0])
        else:
            for f in f_ladder:
                # the flattest model scans the most this f_M allows
                flat_ok = recall_at(f, dims[-1]) >= target
                if not flat_ok and f < f_ladder[-1]:
                    continue
                chosen = (f, dims[-1])
                for g in dims[:-1] if flat_ok else ():
                    if (f, g) != (cfg.f_m, dims[0]) \
                            and recall_at(f, g) >= target:
                        chosen = (f, g)
                        break
                break
        self.set_aps_model(*chosen)
        rec = next(r for f, g, r in reversed(tried) if (f, g) == chosen)
        self.aps_calibration = {
            "f_m": self.aps_f_m, "geometry_dim": self.geometry_dim,
            "recall": rec, "target": target, "met": rec >= target,
            "queries": nq, "k": k, "tried": tried}
        if (self.aps_f_m, self.geometry_dim) != before:
            # planning caches key on the version clock: a new table or
            # candidate fraction must reach them
            self.journal.record(reason="aps_calibration")
        return self.aps_calibration

    # ------------------------------------------------------------------
    # Updates (paper §3 Adaptive Incremental Maintenance - data path)
    # ------------------------------------------------------------------

    def _route_to_base(self, x: np.ndarray) -> np.ndarray:
        """Vectorized top-down routing to the nearest base partition."""
        L = len(self.levels)
        n = x.shape[0]
        if L == 1:
            return kmeans.assign(x, self.levels[0].centroids)
        # nearest top partition for all points
        cur = kmeans.assign(x, self.levels[-1].centroids).astype(np.int64)
        for l in range(L - 1, 0, -1):
            level = self.levels[l]
            below = self.levels[l - 1]
            nxt = np.empty(n, dtype=np.int64)
            for p in np.unique(cur):
                sel = np.where(cur == p)[0]
                child = level.children[p]
                if len(child) == 0:  # empty group: fall back to global
                    nxt[sel] = kmeans.assign(x[sel], below.centroids)
                    continue
                sub = kmeans.assign(x[sel], below.centroids[child])
                nxt[sel] = child[sub]
            cur = nxt
        return cur

    def insert(self, x: np.ndarray, ids: np.ndarray) -> None:
        x = np.ascontiguousarray(x, dtype=np.float32)
        ids = np.asarray(ids, dtype=np.int64)
        if x.shape[0] == 0:
            return
        self._max_norm_sq = max(self._max_norm_sq, float(np.max(
            np.sum(x.astype(np.float64) ** 2, axis=1), initial=0.0)))
        self._aug_extra = [None] * len(self.levels)
        assign = self._route_to_base(x)
        self.journal.record(dirty=np.unique(assign), reason="insert")
        lvl0 = self.levels[0]
        for j in np.unique(assign):
            sel = assign == j
            lvl0.vectors[j] = np.concatenate([lvl0.vectors[j], x[sel]])
            lvl0.ids[j] = np.concatenate([lvl0.ids[j], ids[sel]])
            lvl0.sqnorms[j] = np.concatenate(
                [lvl0.sqnorms[j],
                 np.sum(x[sel].astype(np.float64) ** 2, 1).astype(np.float32)])
        for ext, j in zip(ids, assign):
            self.id_map[int(ext)] = int(j)

    def delete(self, ids: np.ndarray) -> int:
        """Delete by external id with immediate compaction; returns #removed."""
        ids = np.asarray(ids, dtype=np.int64)
        by_part: Dict[int, list] = {}
        removed = 0
        for ext in ids:
            j = self.id_map.pop(int(ext), None)
            if j is not None:
                by_part.setdefault(j, []).append(int(ext))
        if by_part:
            self.journal.record(dirty=by_part.keys(), reason="delete")
        lvl0 = self.levels[0]
        for j, exts in by_part.items():
            mask = ~np.isin(lvl0.ids[j], np.asarray(exts, dtype=np.int64))
            removed += int((~mask).sum())
            lvl0.vectors[j] = np.ascontiguousarray(lvl0.vectors[j][mask])
            lvl0.ids[j] = lvl0.ids[j][mask]
            lvl0.sqnorms[j] = lvl0.sqnorms[j][mask]
        return removed

    # ------------------------------------------------------------------
    # Durability (core/durability.py, docs/durability.md)
    # ------------------------------------------------------------------

    def save(self, root: str) -> dict:
        """Durable save: a full atomic checkpoint under ``root`` (next
        free generation, CRC-manifested, fingerprinted).  Returns the
        manifest.  ``root`` may already hold a WAL + older generations —
        the new checkpoint supersedes them."""
        from .durability import save_index  # late: avoid import cycle
        return save_index(self, root)

    @classmethod
    def load(cls, root: str) -> "QuakeIndex":
        """Load the newest *valid* checkpoint under ``root``, replay any
        WAL suffix, and verify the stored fingerprint — the full
        recovery path (``durability.recover_index``).  Raises
        ``durability.RecoveryError`` when nothing valid survives."""
        from .durability import recover_index  # late: avoid import cycle
        idx, _report = recover_index(root)
        return idx

    # ------------------------------------------------------------------
    # Introspection
    # ------------------------------------------------------------------

    @property
    def version(self) -> int:
        """Monotonic mutation clock, backed by the journal.  Snapshot
        caches fingerprint on it and ask ``journal.delta_since(v)`` for the
        cheap (dirty-partition patch) refresh path."""
        return self.journal.version

    @property
    def num_vectors(self) -> int:
        return sum(len(v) for v in self.levels[0].vectors)

    @property
    def num_partitions(self) -> int:
        return self.levels[0].num_partitions

    def check_invariants(self) -> None:
        """Structural invariants used by property tests."""
        lvl0 = self.levels[0]
        assert len(lvl0.vectors) == len(lvl0.ids) == lvl0.num_partitions
        for v, i, s in zip(lvl0.vectors, lvl0.ids, lvl0.sqnorms):
            assert v.shape[0] == i.shape[0] == s.shape[0]
            assert v.shape[1] == self.dim
        all_ids = np.concatenate([i for i in lvl0.ids]) if \
            lvl0.num_partitions else np.zeros(0)
        assert len(all_ids) == len(set(all_ids.tolist())) == len(self.id_map)
        for ext, j in self.id_map.items():
            assert 0 <= j < lvl0.num_partitions
        # parent/child coherence
        for l in range(1, len(self.levels)):
            level = self.levels[l]
            below = self.levels[l - 1]
            below_n = below.num_partitions
            seen = np.concatenate([c for c in level.children]) if \
                level.num_partitions else np.zeros(0, dtype=np.int64)
            assert len(seen) == below_n, (len(seen), below_n)
            assert len(np.unique(seen)) == below_n
            if len(seen):
                assert seen.min() >= 0 and seen.max() < below_n
            assert below.parent is not None and len(below.parent) == below_n
            for pj in range(level.num_partitions):
                assert (below.parent[level.children[pj]] == pj).all()

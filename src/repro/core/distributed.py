"""Mesh-sharded Quake serving engine — the TPU adaptation of NUMA-aware
query processing (paper §6, Algorithm 2).

Mapping (see DESIGN.md §3):

  NUMA node                  ->  TPU chip (HBM = local memory)
  round-robin partition      ->  partition axis sharded over ("pod","data")
  placement
  worker threads scan local  ->  SPMD: every device scans only its resident
  partitions                     partition shard (shard_map)
  coordinator merges every   ->  per-round hierarchical top-k merge
  T_wait + recall check          (all_gather over the partition axes) +
                                 all-reduced APS recall estimate; a
                                 lax.while_loop exits when every query in the
                                 batch has met its recall target
  work stealing              ->  none (SPMD lock-step); balance is structural,
                                 maintained by the cost model's split policy

The engine serves *snapshots* of the dynamic index (copy-on-write semantics,
paper §8.2): ``IndexSnapshot.from_index`` pads the base level into a dense
``(P, S_cap, d)`` tensor.  Three compiled search paths:

  * ``search_fixed``     — static nprobe per query (baseline; static HLO,
                           the roofline reference point).
  * ``search_adaptive``  — APS rounds in a ``lax.while_loop``; each round
                           every device scans its next ``chunk`` best local
                           partitions for every active query (Algorithm 2).
  * ``search_bruteforce``— exact scan of the full shard (ground truth, the
                           large-batch multi-query policy, and the two-tower
                           ``retrieval_cand`` path).

Query parallelism: the batch is sharded over the ``model`` axis when one is
present, so a (pod, data, model) mesh gives partition parallelism x query
parallelism — the 2-D analogue of "threads within a NUMA node".
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map
from ..kernels.ref import HIGHEST, MASK_DIST, merge_topk, pairwise_l2_sq
from . import geometry
from .index import QuakeIndex

Array = jax.Array


# ---------------------------------------------------------------------------
# Snapshot
# ---------------------------------------------------------------------------

# Row-replacement scatters behind apply_delta.  The donated variant
# updates the operand buffer in place (XLA input-output aliasing): the
# refresh cost is O(dirty rows), not O(snapshot) — but the donated array
# is consumed.  Bucket padding in build_patch keeps the set of compiled
# (shape, dtype) specializations small.
_scatter_rows = jax.jit(lambda a, sel, u: a.at[sel].set(u))
_scatter_rows_donated = jax.jit(lambda a, sel, u: a.at[sel].set(u),
                                donate_argnums=(0,))


@dataclass
class SnapshotPatch:
    """Host-side replacement rows for a subset of snapshot partitions —
    the unit of incremental (copy-on-write) refresh.  Built against a fixed
    slot capacity by ``IndexSnapshot.build_patch``; consumed on device by
    ``IndexSnapshot.apply_delta`` and by host-side mirrors (executor
    ``_flat_ids``/``_sizes``)."""
    rows: np.ndarray        # (R,) int32 partition ids, sorted; the tail
                            # may duplicate the last row (bucket padding —
                            # identical updates, inert under scatter)
    data: np.ndarray        # (R, S_cap, d) float32
    ids: np.ndarray         # (R, S_cap) int32, -1 on padding
    centroids: np.ndarray   # (R, d) float32
    sizes: np.ndarray       # (R,) int32


@jax.tree_util.register_dataclass
@dataclass
class IndexSnapshot:
    """Dense, shardable view of the base level.

    data:      (P, S_cap, d)  padded partition contents
    ids:       (P, S_cap)     external ids (int32), -1 on padding
    centroids: (P, d)
    sizes:     (P,)           true sizes (0 marks padding partitions)
    beta_table:(1024,)        precomputed regularized-incomplete-beta values
    """
    data: Array
    ids: Array
    centroids: Array
    sizes: Array
    beta_table: Array
    scales: Optional[Array] = None   # (P, S_cap) per-slot dequant scales
                                     # when data holds int8 codes (§8.2)

    @property
    def num_partitions(self) -> int:
        return self.data.shape[0]

    @property
    def capacity(self) -> int:
        return self.data.shape[1]

    @property
    def dim(self) -> int:
        return self.data.shape[2]

    @staticmethod
    def align_capacity(s_cap: int) -> int:
        """Round a slot capacity up so Pallas scan tiles divide it exactly:
        next power of two below 512, next multiple of 512 above."""
        s_cap = max(s_cap, 8)
        if s_cap <= 512:
            p2 = 8
            while p2 < s_cap:
                p2 *= 2
            return p2
        return -(-s_cap // 512) * 512

    @staticmethod
    def from_index(index: QuakeIndex, pad_partitions_to: int = 1,
                   capacity: Optional[int] = None,
                   headroom: float = 1.0,
                   allow_truncation: bool = False) -> "IndexSnapshot":
        """Dense snapshot of the base level.

        ``headroom`` pads the slot capacity beyond the current largest
        partition (>1.0 leaves slack so subsequent ``apply_delta`` patches
        rarely force a reshape).  An explicit ``capacity`` smaller than the
        largest partition raises unless ``allow_truncation=True``; with
        truncation allowed the recorded ``sizes`` are clamped to what was
        actually stored, so they always agree with the ``ids >= 0`` mask.
        """
        lvl0 = index.levels[0]
        p_real = lvl0.num_partitions
        p = ((p_real + pad_partitions_to - 1)
             // pad_partitions_to) * pad_partitions_to
        sizes = np.zeros(p, dtype=np.int32)
        sizes[:p_real] = lvl0.sizes()
        if capacity is None:
            s_cap = max(int(math.ceil(int(sizes.max(initial=0))
                                      * max(headroom, 1.0))), 1)
        else:
            s_cap = capacity
        s_cap = IndexSnapshot.align_capacity(s_cap)
        if int(sizes.max(initial=0)) > s_cap and not allow_truncation:
            raise ValueError(
                f"IndexSnapshot capacity {s_cap} would truncate a "
                f"partition of size {int(sizes.max())}; pass "
                "allow_truncation=True to store a lossy snapshot")
        d = index.dim
        data = np.zeros((p, s_cap, d), dtype=np.float32)
        ids = np.full((p, s_cap), -1, dtype=np.int32)
        for j in range(p_real):
            s = min(int(sizes[j]), s_cap)
            sizes[j] = s          # recorded size == stored size, always
            data[j, :s] = lvl0.vectors[j][:s]
            ext = lvl0.ids[j][:s]
            if len(ext) and int(ext.max()) > np.iinfo(np.int32).max:
                raise ValueError(
                    "IndexSnapshot stores external ids as int32; id "
                    f"{int(ext.max())} does not fit (partition {j})")
            ids[j, :s] = ext
        cents = np.zeros((p, d), dtype=np.float32)
        cents[:p_real] = lvl0.centroids
        # padding partitions: park centroids far away so routing never
        # selects them (MASK via sizes==0 also applies)
        if p > p_real:
            cents[p_real:] = 1e6
        table = index._beta_table     # the index's (fitted) cap model
        return IndexSnapshot(
            data=jnp.asarray(data), ids=jnp.asarray(ids),
            centroids=jnp.asarray(cents), sizes=jnp.asarray(sizes),
            beta_table=jnp.asarray(table))

    # ------------------------------------------------------------------
    # Incremental (copy-on-write) refresh
    # ------------------------------------------------------------------

    @staticmethod
    def build_patch(index: QuakeIndex, rows, capacity: int,
                    bucket: int = 16) -> "SnapshotPatch":
        """Host-side patch for ``rows`` (level-0 partition ids) against a
        snapshot of slot capacity ``capacity``.  Raises ``ValueError`` if a
        row no longer fits — the caller falls back to a full rebuild.

        ``bucket`` floors the padded row count; above it the count rounds
        to the next power of two (padding duplicates the last row — an
        identical-update no-op under scatter).  Each distinct patch shape
        pays one scatter compile per process, so the power-of-two ladder
        caps that at ~log2(P) compiles total regardless of how the dirty
        set size drifts across refreshes."""
        lvl0 = index.levels[0]
        uniq = sorted({int(j) for j in rows})
        if uniq and (uniq[0] < 0 or uniq[-1] >= lvl0.num_partitions):
            raise ValueError(f"patch rows {uniq} outside partition "
                             f"directory [0, {lvl0.num_partitions})")
        if uniq and bucket > 1:
            r_pad = bucket
            while r_pad < len(uniq):
                r_pad *= 2
            uniq = uniq + [uniq[-1]] * (r_pad - len(uniq))
        rows = np.asarray(uniq, dtype=np.int32)
        r, d = len(rows), index.dim
        data = np.zeros((r, capacity, d), dtype=np.float32)
        ids = np.full((r, capacity), -1, dtype=np.int32)
        sizes = np.zeros(r, dtype=np.int32)
        for i, j in enumerate(rows):
            s = len(lvl0.vectors[j])
            if s > capacity:
                raise ValueError(
                    f"partition {j} (size {s}) exceeds snapshot "
                    f"capacity {capacity}")
            ext = lvl0.ids[j]
            if s and int(ext.max()) > np.iinfo(np.int32).max:
                raise ValueError(
                    "IndexSnapshot stores external ids as int32; id "
                    f"{int(ext.max())} does not fit (partition {j})")
            data[i, :s] = lvl0.vectors[j]
            ids[i, :s] = ext
            sizes[i] = s
        cents = np.ascontiguousarray(
            lvl0.centroids[rows], dtype=np.float32) if r else \
            np.zeros((0, d), dtype=np.float32)
        return SnapshotPatch(rows=rows, data=data, ids=ids,
                             centroids=cents, sizes=sizes)

    def apply_delta(self, patch: "SnapshotPatch",
                    donate: bool = False) -> "IndexSnapshot":
        """Return a new snapshot with the patch rows replaced on device;
        only the patch moves host->device.

        ``donate=False`` (true copy-on-write): the previous snapshot stays
        readable — in-flight readers keep serving from it — at the cost of
        an O(P*S_cap*d) device-side buffer copy.  ``donate=True`` updates
        the donated buffers in place (the patch cost is O(dirty rows), the
        executor steady-state) but *consumes* this snapshot: the caller
        must own it exclusively, and any handle to it is dead afterwards.
        """
        if self.scales is not None:
            raise ValueError("apply_delta does not support quantized "
                             "(int8) snapshots; rebuild instead")
        if len(patch.rows) == 0:
            return self
        if int(patch.rows.max()) >= self.num_partitions:
            raise ValueError("patch rows outside snapshot partition range")
        if patch.data.shape[1] != self.capacity:
            raise ValueError(
                f"patch capacity {patch.data.shape[1]} != snapshot "
                f"capacity {self.capacity}")
        sel = jnp.asarray(patch.rows)
        set_rows = _scatter_rows_donated if donate else _scatter_rows
        return IndexSnapshot(
            data=set_rows(self.data, sel,
                          jnp.asarray(patch.data).astype(self.data.dtype)),
            ids=set_rows(self.ids, sel,
                         jnp.asarray(patch.ids).astype(self.ids.dtype)),
            centroids=set_rows(
                self.centroids, sel,
                jnp.asarray(patch.centroids).astype(self.centroids.dtype)),
            sizes=set_rows(self.sizes, sel,
                           jnp.asarray(patch.sizes).astype(self.sizes.dtype)),
            beta_table=self.beta_table,
            scales=None)

    @staticmethod
    def synthetic(p: int, s_cap: int, d: int, seed: int = 0,
                  dtype=jnp.float32) -> "IndexSnapshot":
        """Random snapshot for benchmarks / dry-runs (no host data)."""
        k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
        cents = jax.random.normal(k1, (p, d), dtype) * 3.0
        noise = jax.random.normal(k2, (p, s_cap, d), dtype)
        data = cents[:, None, :] + noise
        ids = jnp.arange(p * s_cap, dtype=jnp.int32).reshape(p, s_cap)
        sizes = jnp.full((p,), s_cap, jnp.int32)
        table = jnp.asarray(geometry.betainc_table(d))
        return IndexSnapshot(data, ids, cents, sizes, table)


# ---------------------------------------------------------------------------
# Sharded engine
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class EngineConfig:
    metric: str = "l2"
    k: int = 100
    nprobe: int = 16             # search_fixed probes (per whole index)
    chunk: int = 2               # adaptive: local partitions per round
    max_rounds: int = 16
    recall_target: float = 0.9
    batch_axis: Optional[str] = "model"   # query-parallel axis (None = off)
    part_axes: Tuple[str, ...] = ("data",)  # partition-parallel axes
    # --- scan implementation (§Perf hillclimb) ---
    #  "gather":       per-query gather + einsum (paper-faithful XLA
    #                  baseline; every scanned byte moves ~3x through HBM)
    #  "union_jnp":    batch-deduped union scan (paper §7.4 multi-query
    #                  policy applied per shard) via gather + one GEMM
    #  "union_pallas": union scan through the scalar-prefetch Pallas kernel
    #                  — each selected block streams HBM->VMEM exactly once
    scan_impl: str = "gather"
    union_cap: Optional[int] = None  # union size; None = B_loc * n_sel
                                     # (set lower under read skew — hot
                                     # partitions dedupe across the batch)
    storage_dtype: str = "f32"       # "bf16" halves scan traffic (beyond-
                                     # paper; distances accumulate in f32)
    rounds: Optional[int] = None     # search_batch early-exit round budget
                                     # (APS mode): None = as many geometric
                                     # rounds as the plan needs, 1 = one
                                     # monolithic fixed-plan scan


class ShardedQuakeEngine:
    """Compiled search over a sharded snapshot."""

    def __init__(self, mesh: Mesh, config: EngineConfig):
        self.mesh = mesh
        self.cfg = config
        axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        self.n_part_shards = int(np.prod([axis_sizes[a]
                                          for a in config.part_axes]))
        self.batch_axis = config.batch_axis if (
            config.batch_axis in mesh.axis_names) else None
        self.n_batch_shards = axis_sizes.get(self.batch_axis, 1) \
            if self.batch_axis else 1
        # journal-aware sharded snapshot cache (refresh_snapshot)
        self._snap: Optional[IndexSnapshot] = None
        self._snap_version = -1
        self._host_sizes: Optional[np.ndarray] = None  # (P,) host mirror
        self._planner_cache = None   # multiquery.PlannerCache (search_batch)
        self._planned_fns = {}   # n_union -> jitted planned-batch executor
        self.full_rebuilds = 0
        self.delta_refreshes = 0

    # ---- sharding specs ----
    def snapshot_spec(self) -> IndexSnapshot:
        pa = P(self.cfg.part_axes)
        return IndexSnapshot(
            data=pa, ids=pa, centroids=pa, sizes=pa, beta_table=P(),
            scales=pa if self.cfg.storage_dtype == "int8" else None)

    def shard_snapshot(self, snap: IndexSnapshot) -> IndexSnapshot:
        pa = NamedSharding(self.mesh, P(self.cfg.part_axes))
        rep = NamedSharding(self.mesh, P())
        data, scales = snap.data, None
        if self.cfg.storage_dtype == "bf16":
            data = data.astype(jnp.bfloat16)
        elif self.cfg.storage_dtype == "int8":
            # IVF residual SQ8 (paper §8.2): quantize x - c_j, the exact
            # query-centroid term is restored in-kernel
            from ..kernels.scan_topk_indexed import quantize_int8_residual
            data, scales = quantize_int8_residual(snap.data, snap.centroids)
            scales = jax.device_put(scales, pa)
        return IndexSnapshot(
            data=jax.device_put(data, pa),
            ids=jax.device_put(snap.ids, pa),
            centroids=jax.device_put(snap.centroids, pa),
            sizes=jax.device_put(snap.sizes, pa),
            beta_table=jax.device_put(snap.beta_table, rep),
            scales=scales)

    def refresh_snapshot(self, index: QuakeIndex) -> IndexSnapshot:
        """Cached device-sharded snapshot of the dynamic index, kept
        coherent through the index's mutation journal (the same
        invalidation protocol the batched executor uses).  Content deltas
        confined to known partitions patch only the dirty rows of the
        resident sharded arrays — no host re-densify, no full transfer;
        structural changes, int8 storage (rows would need requantizing),
        capacity overflow, or a trimmed journal re-shard a full rebuild.
        """
        from .maintenance import fit_to_capacity  # late: import cycle
        fixed = fit_to_capacity(index, index.config.snapshot_headroom)
        if self._snap is not None and self.cfg.storage_dtype != "int8":
            delta = index.journal.delta_since(self._snap_version)
            if delta is not None and not delta.structural:
                lvl0 = index.levels[0]
                p_real = lvl0.num_partitions
                dirty = sorted(j for j in delta.dirty if j < p_real)
                if not dirty:
                    self._snap_version = index.version
                    return self._snap
                cap = self._snap.capacity
                max_frac = index.config.snapshot_max_dirty_frac
                if (len(dirty) <= max_frac * max(p_real, 1)
                        and p_real <= self._snap.num_partitions
                        and max(len(lvl0.vectors[j]) for j in dirty) <= cap):
                    try:
                        patch = IndexSnapshot.build_patch(index, dirty, cap)
                        # the engine owns its cached sharded snapshot:
                        # in-place row patch; handles returned from earlier
                        # refresh_snapshot calls are consumed
                        self._snap = self._snap.apply_delta(patch,
                                                            donate=True)
                    except ValueError:
                        pass
                    else:
                        self._host_sizes[patch.rows] = patch.sizes
                        self._snap_version = index.version
                        self.delta_refreshes += 1
                        return self._snap
        host = IndexSnapshot.from_index(
            index, pad_partitions_to=self.n_part_shards, capacity=fixed,
            headroom=index.config.snapshot_headroom)
        self._snap = self.shard_snapshot(host)
        self._host_sizes = np.array(host.sizes)
        self._snap_version = index.version
        self.full_rebuilds += 1
        return self._snap

    def pad_queries(self, q: Array) -> Array:
        b = q.shape[0]
        bs = self.n_batch_shards
        bp = ((b + bs - 1) // bs) * bs
        if bp != b:
            q = jnp.concatenate(
                [q, jnp.zeros((bp - b, q.shape[1]), q.dtype)])
        return q

    # ------------------------------------------------------------------
    # shard-local primitives
    # ------------------------------------------------------------------

    def _local_centroid_dists(self, q: Array, snap: IndexSnapshot) -> Array:
        """(B_loc, P_loc) centroid distances in minimization convention,
        masked on padding partitions."""
        if self.cfg.metric == "l2":
            d = pairwise_l2_sq(q, snap.centroids)
        else:
            d = -jnp.matmul(q, snap.centroids.T, precision=HIGHEST)
        return jnp.where(snap.sizes[None, :] > 0, d, MASK_DIST)

    def _scan_selected(self, q: Array, snap: IndexSnapshot,
                       sel: Array) -> Tuple[Array, Array]:
        """Scan ``sel`` (B_loc, n_sel) local partitions per query; returns
        (dists (B_loc, n_sel*S), ids) in minimization convention.

        This gather + batched-GEMV *is* the memory-bound hot loop: each
        selected partition block is streamed from HBM exactly once.
        """
        blocks = jnp.take(snap.data, sel, axis=0)       # (B, n, S, d)
        bids = jnp.take(snap.ids, sel, axis=0)          # (B, n, S)
        valid = bids >= 0
        blocks32 = blocks.astype(jnp.float32)
        if self.cfg.metric == "l2":
            x2 = jnp.sum(blocks32 * blocks32, axis=-1)
            qx = jnp.einsum("bnsd,bd->bns", blocks32, q,
                            preferred_element_type=jnp.float32,
                            precision=HIGHEST)
            q2 = jnp.sum(q * q, axis=-1)[:, None, None]
            dist = x2 - 2.0 * qx + q2
        else:
            dist = -jnp.einsum("bnsd,bd->bns", blocks32, q,
                               preferred_element_type=jnp.float32,
                               precision=HIGHEST)
        dist = jnp.where(valid, dist, MASK_DIST)
        b = dist.shape[0]
        return dist.reshape(b, -1), bids.reshape(b, -1)

    def _scan_packed(self, q: Array, snap: IndexSnapshot, selected: Array,
                     k: int, n_union: int,
                     priority: Optional[Array] = None
                     ) -> Tuple[Array, Array]:
        """Packed union scan of a dense ``selected`` (B, P_loc) bool probe
        matrix: ``pack_union`` (frequency-ranked with an optional anchor
        ``priority``, so ``n_union`` truncation keeps the partitions most
        queries probe and never a query's nearest) + one packed top-k scan
        in the engine's storage dtype.  Returns (dists (B, k), external
        ids (B, k)) ascending.
        """
        from ..kernels import ops as kops
        cfg = self.cfg
        sel_u, qmask = kops.pack_union(selected, n_union,
                                       priority=priority)  # (U,), (B, U)
        valid = snap.ids >= 0                            # (P_loc, S)
        if snap.scales is not None:                      # int8 residuals
            d, flat = kops.scan_selected_topk_q8(
                q, snap.data, snap.scales, valid, sel_u, qmask, k,
                metric=cfg.metric, centroids=snap.centroids)
        else:
            impl = "pallas" if cfg.scan_impl == "union_pallas" else "jnp"
            d, flat = kops.scan_selected_topk(
                q, snap.data, valid, sel_u, qmask, k, metric=cfg.metric,
                impl=impl)
        ids_flat = snap.ids.reshape(-1)
        ext = jnp.where(flat >= 0,
                        jnp.take(ids_flat, jnp.maximum(flat, 0)), -1)
        return d, ext.astype(jnp.int32)

    def _scan_union_topk(self, q: Array, snap: IndexSnapshot, sel: Array,
                         k: int) -> Tuple[Array, Array]:
        """Union-deduped scan of per-query selections ``sel`` (B, n):
        the batch's selected partitions are packed into one static union and
        each block is scanned once for the whole batch (paper §7.4 policy),
        preserving per-query probe semantics via a selection mask.

        Returns (dists (B, k), external ids (B, k)) ascending.
        """
        cfg = self.cfg
        b, n_sel = sel.shape
        p_loc = snap.num_partitions
        n_union = min(cfg.union_cap or b * n_sel, p_loc)
        selected = jnp.zeros((b, p_loc), jnp.bool_).at[
            jnp.arange(b)[:, None], sel].set(True)
        # sel arrives best-first (top_k order): column 0 is each query's
        # nearest local partition — anchor it above the frequency ranking
        anchor = jnp.zeros((p_loc,), jnp.bool_).at[sel[:, 0]].set(True)
        return self._scan_packed(q, snap, selected, k, n_union,
                                 priority=anchor.astype(jnp.int32) * (b + 1))

    def _merge_global(self, d_loc: Array, i_loc: Array, k: int
                      ) -> Tuple[Array, Array]:
        """Hierarchical top-k merge across the partition shards (the
        coordinator-thread analogue): all_gather local candidates, re-select.
        Collective volume: B * n_shards * k * 8 bytes — negligible next to
        the scan traffic."""
        axes = self.cfg.part_axes
        dg = jax.lax.all_gather(d_loc, axes, axis=1, tiled=True)
        ig = jax.lax.all_gather(i_loc, axes, axis=1, tiled=True)
        vals, sel = jax.lax.top_k(-dg, k)
        return -vals, jnp.take_along_axis(ig, sel, axis=1)

    # ------------------------------------------------------------------
    # fixed-nprobe search (static baseline)
    # ------------------------------------------------------------------

    def _search_fixed_local(self, q: Array, snap: IndexSnapshot
                            ) -> Tuple[Array, Array]:
        cfg = self.cfg
        # per-shard probe share, ceil so the union covers >= nprobe
        n_loc = max(1, -(-cfg.nprobe // self.n_part_shards))
        n_loc = min(n_loc, snap.num_partitions)
        cd = self._local_centroid_dists(q, snap)
        _, sel = jax.lax.top_k(-cd, n_loc)              # (B, n_loc)
        if cfg.scan_impl != "gather":
            d_loc, i_loc = self._scan_union_topk(q, snap, sel, cfg.k)
            return self._merge_global(d_loc, i_loc, cfg.k)
        d, i = self._scan_selected(q, snap, sel)
        k = min(cfg.k, d.shape[1])
        vals, pos = jax.lax.top_k(-d, k)
        d_loc, i_loc = -vals, jnp.take_along_axis(i, pos, axis=1)
        if k < cfg.k:
            pad_d = jnp.full((d.shape[0], cfg.k - k), MASK_DIST)
            pad_i = jnp.full((d.shape[0], cfg.k - k), -1, i_loc.dtype)
            d_loc = jnp.concatenate([d_loc, pad_d], axis=1)
            i_loc = jnp.concatenate([i_loc, pad_i], axis=1)
        return self._merge_global(d_loc, i_loc, cfg.k)

    # ------------------------------------------------------------------
    # adaptive search (APS rounds; Algorithm 2)
    # ------------------------------------------------------------------

    def _search_adaptive_local(self, q: Array, snap: IndexSnapshot
                               ) -> Tuple[Array, Array, Array, Array]:
        cfg = self.cfg
        b = q.shape[0]
        p_loc = snap.num_partitions
        chunk = min(cfg.chunk, p_loc)
        axes = cfg.part_axes

        cd = self._local_centroid_dists(q, snap)         # (B, P_loc)
        # global nearest centroid distance (for c0 and margins)
        d0 = jax.lax.pmin(jnp.min(cd, axis=1), axes)     # (B,)
        # ||ci - c0||: c0 gathered via a global argmin — emulate with a
        # masked select + psum broadcast of the winning centroid.
        is_min = (cd <= d0[:, None]).astype(q.dtype)
        # tie-break: normalize so exactly weight-1 total across all shards
        w = is_min / jnp.maximum(jax.lax.psum(
            jnp.sum(is_min, axis=1), axes), 1.0)[:, None]
        c0 = jax.lax.psum(jnp.matmul(w, snap.centroids, precision=HIGHEST),
                          axes)                          # (B, d)
        cc = jnp.sqrt(jnp.maximum(pairwise_l2_sq(c0, snap.centroids), 1e-12))

        def probs(rho_sq: Array, scanned: Array) -> Tuple[Array, Array]:
            """Global recall estimate r per query (Eqs. 7-9 across shards)."""
            rho = jnp.sqrt(jnp.maximum(rho_sq, 1e-30))[:, None]
            h = (cd - d0[:, None]) / (2.0 * jnp.maximum(cc, 1e-12))
            v = geometry.cap_fraction(h / rho, snap.beta_table)
            cand = (snap.sizes[None, :] > 0) & (cd > d0[:, None])
            v = jnp.where(cand, v, 0.0)
            tot = jax.lax.psum(jnp.sum(v, axis=1), axes)[:, None]
            vn = jnp.where(tot > 0, v / jnp.maximum(tot, 1e-20), 0.0)
            log1m = jnp.where(cand, jnp.log1p(-jnp.clip(vn, 0, 1 - 1e-7)),
                              0.0)
            p0 = jnp.exp(jax.lax.psum(jnp.sum(log1m, axis=1), axes))
            p0 = jnp.where(tot[:, 0] > 0, p0, 1.0)
            p = (1.0 - p0[:, None]) * vn
            r = p0 + jax.lax.psum(
                jnp.sum(jnp.where(scanned, p, 0.0), axis=1), axes)
            return r, p

        def rho_from_topk(td: Array) -> Array:
            kth = td[:, -1]
            if cfg.metric == "l2":
                return jnp.maximum(kth, 0.0)
            # MIPS: rho^2 in augmented space; snapshot data pre-normalized
            # geometry uses max-norm from centroid table (approximation)
            q2 = jnp.sum(q * q, axis=-1)
            m2 = jnp.max(jnp.sum(snap.centroids ** 2, axis=-1))
            m2 = jax.lax.pmax(m2, axes)
            return jnp.maximum(q2 + m2 + 2.0 * kth, 0.0)

        def body(state):
            rnd, scanned, td, ti, r = state
            # next chunk of unscanned local partitions by probability order
            # (centroid-distance order is probability order for fixed rho)
            masked = jnp.where(scanned, MASK_DIST, cd)
            _, sel = jax.lax.top_k(-masked, chunk)       # (B, chunk)
            newly = jax.nn.one_hot(sel, p_loc, dtype=jnp.bool_).any(axis=1)
            scanned2 = scanned | newly
            if cfg.scan_impl != "gather":
                d, i = self._scan_union_topk(q, snap, sel, cfg.k)
            else:
                d, i = self._scan_selected(q, snap, sel)
            td2, ti2 = merge_topk(td, ti, d, i, cfg.k)
            tdg, _ = self._merge_global(td2, ti2, cfg.k)
            r2, _ = probs(rho_from_topk(tdg), scanned2)
            return rnd + 1, scanned2, td2, ti2, r2

        def cond(state):
            rnd, scanned, td, ti, r = state
            unscanned = jax.lax.psum(
                jnp.sum(~scanned, axis=1), axes)         # (B,)
            active = (r < cfg.recall_target) & (unscanned > 0)
            return (rnd < cfg.max_rounds) & jnp.any(active)

        init = (jnp.zeros((), jnp.int32),
                jnp.zeros((b, p_loc), jnp.bool_),
                jnp.full((b, cfg.k), MASK_DIST, jnp.float32),
                jnp.full((b, cfg.k), -1, jnp.int32),
                jnp.zeros((b,), jnp.float32))
        state = body(init)  # round 1 always scans (initializes rho)
        rnd, scanned, td, ti, r = jax.lax.while_loop(cond, body, state)
        dg, ig = self._merge_global(td, ti, cfg.k)
        nprobe = jax.lax.psum(jnp.sum(scanned, axis=1), axes)
        return dg, ig, r, nprobe

    # ------------------------------------------------------------------
    # brute force (exact; multi-query policy / ground truth / retrieval)
    # ------------------------------------------------------------------

    def _search_brute_local(self, q: Array, snap: IndexSnapshot
                            ) -> Tuple[Array, Array]:
        cfg = self.cfg
        p_loc, s_cap, d = snap.data.shape
        flat = snap.data.reshape(p_loc * s_cap, d)
        fids = snap.ids.reshape(p_loc * s_cap)
        if cfg.metric == "l2":
            dist = pairwise_l2_sq(q, flat)
        else:
            dist = -jnp.matmul(q, flat.T, precision=HIGHEST)
        dist = jnp.where(fids[None, :] >= 0, dist, MASK_DIST)
        k = min(cfg.k, dist.shape[1])
        vals, pos = jax.lax.top_k(-dist, k)
        return self._merge_global(-vals, fids[pos], cfg.k)

    # ------------------------------------------------------------------
    # public jitted entry points
    # ------------------------------------------------------------------

    def query_spec(self) -> P:
        return P(self.batch_axis) if self.batch_axis else P()

    def mapped_fn(self, kind: str):
        """The shard_map'd (unjitted) search callable — used directly by the
        dry-run lowering and wrapped by the jitted properties below."""
        fn, n_out = {"fixed": (self._search_fixed_local, 2),
                     "adaptive": (self._search_adaptive_local, 4),
                     "brute": (self._search_brute_local, 2)}[kind]
        qspec = self.query_spec()
        out_specs = tuple([qspec] * n_out)
        return shard_map(
            fn, mesh=self.mesh,
            in_specs=(qspec, self.snapshot_spec()),
            out_specs=out_specs if n_out > 1 else qspec,
            check_vma=False)

    @functools.cached_property
    def search_fixed(self):
        return jax.jit(self.mapped_fn("fixed"))

    @functools.cached_property
    def search_adaptive(self):
        return jax.jit(self.mapped_fn("adaptive"))

    @functools.cached_property
    def search_bruteforce(self):
        return jax.jit(self.mapped_fn("brute"))

    # ------------------------------------------------------------------
    # planner-driven multi-query entry (shares core.multiquery.plan_batch)
    # ------------------------------------------------------------------

    def _search_planned_local(self, q: Array, snap: IndexSnapshot,
                              selected: Array, anchor: Array, *,
                              n_union: int) -> Tuple[Array, Array]:
        prio = anchor.astype(jnp.int32) * (selected.shape[0] + 1)
        d_loc, i_loc = self._scan_packed(q, snap, selected, self.cfg.k,
                                         n_union, priority=prio)
        return self._merge_global(d_loc, i_loc, self.cfg.k)

    def _planned_fn(self, n_union: int):
        """Jitted SPMD executor for a planned batch: the (B, P) probe
        matrix is sharded with the snapshot (batch axis x partition axes),
        each device packs its local slice of the union (``pack_union``)
        and scans it once, and the per-round hierarchical merge combines
        shard-local top-k.  One compile per bucketed local-union size,
        cached per engine instance (a class-level lru_cache would pin
        engines and their compiled closures for the process lifetime)."""
        cached = self._planned_fns.get(n_union)
        if cached is not None:
            return cached
        qspec = self.query_spec()
        sel_spec = P(self.batch_axis, self.cfg.part_axes) \
            if self.batch_axis else P(None, self.cfg.part_axes)
        fn = functools.partial(self._search_planned_local, n_union=n_union)
        jitted = jax.jit(shard_map(
            fn, mesh=self.mesh,
            in_specs=(qspec, self.snapshot_spec(), sel_spec,
                      P(self.cfg.part_axes)),
            out_specs=(qspec, qspec), check_vma=False))
        self._planned_fns[n_union] = jitted
        return jitted

    def search_batch(self, index: QuakeIndex, queries: np.ndarray,
                     k: Optional[int] = None,
                     nprobe: Optional[int] = None,
                     recall_target: Optional[float] = None,
                     union_cap: Optional[int] = None,
                     rounds: Optional[int] = None):
        """Multi-query search over the sharded snapshot through the *same*
        host batch planner as the device-resident executor
        (``core.multiquery.plan_batch``): per-query probe sets (vectorized
        APS when ``nprobe`` is None) are planned once against the dynamic
        index, then scattered into a dense (B, P) probe matrix whose
        partition axis is sharded with the snapshot — each device packs
        and scans only its local slice of the batch union.  APS-planned
        searches run through the *same* multi-round early-exit loop as
        the host executor (``multiquery.run_round_loop``): per round only
        live queries' rows of the probe matrix are populated, so every
        shard's local pack sees the per-shard slice of the live mask and
        later rounds shrink with the hard tail (``rounds=1``, pinned
        ``nprobe``, or a ``union_cap`` — whose truncation is defined on
        the whole-batch plan — fall back to the one-shot scan).  Returns
        ``multiquery.BatchResult`` (top-``min(k, cfg.k)`` columns).
        """
        from .multiquery import (BatchResult, PlannerCache,  # avoid cycle
                                 plan_batch)
        cfg = self.cfg
        k = cfg.k if k is None else min(k, cfg.k)
        q = np.ascontiguousarray(queries, dtype=np.float32)
        if q.ndim == 1:
            q = q[None, :]
        b = q.shape[0]
        if b == 0:
            return BatchResult(ids=np.zeros((0, k), dtype=np.int64),
                               dists=np.zeros((0, k), dtype=np.float64),
                               nprobe=np.zeros(0, dtype=np.int64))
        snap = self.refresh_snapshot(index)
        # planner state (centroid norms, calibrated radii) rides the same
        # fingerprint protocol as the host executor's caches
        if self._planner_cache is None or \
                self._planner_cache.index is not index:
            self._planner_cache = PlannerCache(index)
        pc = self._planner_cache.ensure_fresh()
        cap = union_cap if union_cap is not None else cfg.union_cap
        rounds = cfg.rounds if rounds is None else rounds
        if rounds is not None and rounds < 1:
            raise ValueError(f"rounds must be >= 1 or None, got {rounds}")
        if nprobe is None and rounds != 1 and cap is None:
            target = recall_target if recall_target is not None \
                else index.config.recall_target
            return self._search_batch_rounds(index, q, k, target, rounds,
                                             snap, pc)
        # cfg.union_cap caps the *plan* (like the host executor), so the
        # returned stats and effective nprobe reflect what was scanned
        plan = plan_batch(index, q, k, nprobe=nprobe,
                          recall_target=recall_target,
                          union_cap=cap,
                          cent_norms=pc._cent_norms, cache=pc)
        qp = self.pad_queries(jnp.asarray(q))
        p_pad = snap.num_partitions
        # the plan's packed union defines the cap semantics + stats; each
        # shard re-packs its local slice of it below (different work: the
        # local union is what the shard's scan grid iterates)
        sel_cols = plan.sel[:plan.n_real]
        selected = np.zeros((qp.shape[0], p_pad), dtype=bool)
        selected[np.ix_(np.arange(b), sel_cols)] = \
            plan.qmask[:, :plan.n_real]
        # static per-shard union size: the largest local share of the
        # batch union, bucketed so recompiles stay rare
        p_loc = p_pad // self.n_part_shards
        u_loc = int(np.bincount(sel_cols // p_loc,
                                minlength=self.n_part_shards).max())
        u_loc = min(max(-(-max(u_loc, 1) // 8) * 8, 1), p_loc)
        anchor = np.zeros(p_pad, dtype=bool)
        anchor[plan.anchor] = True
        d, ids = self._planned_fn(u_loc)(qp, snap, jnp.asarray(selected),
                                         jnp.asarray(anchor))
        d = np.asarray(d, dtype=np.float64)[:b, :k]
        ids = np.asarray(ids)[:b, :k]
        d = np.where(d >= MASK_DIST, np.inf, d)
        ids = np.where(np.isinf(d), -1, ids)
        sizes = self._host_sizes[sel_cols]   # snapshot-refreshed mirror,
                                             # not an O(P) host walk
        return BatchResult(
            ids=ids.astype(np.int64), dists=d,
            partitions_scanned=int(plan.n_real),
            vectors_scanned=int(sizes.sum()),
            comparisons=int((plan.qmask[:, :plan.n_real].astype(np.int64)
                             * sizes[None, :]).sum()),
            nprobe=plan.nprobe, recall_estimate=plan.recall_est)

    def _search_batch_rounds(self, index: QuakeIndex, q: np.ndarray,
                             k: int, target: float,
                             rounds: Optional[int], snap: IndexSnapshot,
                             pc):
        """The engine side of the shared Algorithm-2 round loop: each
        round scatters only live queries' next probe-sequence window into
        the sharded (B, P) probe matrix and reuses the jitted planned-
        batch executor (per-shard ``pack_union`` + packed scan + global
        merge); the shared driver owns the running top-k, the refined
        recall estimate, and the live mask."""
        from .multiquery import (BatchResult, _batch_rho_fn,  # avoid cycle
                                 plan_rounds, run_round_loop)
        b = q.shape[0]
        rplan = plan_rounds(index, q, k, target, cache=pc,
                            cent_norms=pc._cent_norms)
        qp = self.pad_queries(jnp.asarray(q))
        bp = qp.shape[0]
        p_pad = snap.num_partitions
        p_loc = p_pad // self.n_part_shards

        rr = np.broadcast_to(np.arange(b)[:, None], rplan.seq.shape)

        def scan_round(take, kept):
            selected = np.zeros((bp, p_pad), dtype=bool)
            selected[rr[take], rplan.seq[take]] = True
            # static per-shard union size: largest local share, bucketed
            u_loc = int(np.bincount(kept // p_loc,
                                    minlength=self.n_part_shards).max())
            u_loc = min(max(-(-max(u_loc, 1) // 8) * 8, 1), p_loc)
            anchor = np.zeros(p_pad, dtype=bool)   # uncapped: no priority
            d, ids = self._planned_fn(u_loc)(qp, snap,
                                             jnp.asarray(selected),
                                             jnp.asarray(anchor))
            sizes = self._host_sizes[kept]
            st = {"partitions": int(len(kept)),
                  "vectors": int(sizes.sum()),
                  "comparisons": int(
                      self._host_sizes[rplan.seq[take]].sum())}
            return d[:b], ids[:b], st

        td, ti, nprobe, r_est, n_rounds, trace, stats = run_round_loop(
            rplan, k, target, index._beta_table, _batch_rho_fn(index, q),
            scan_round, rounds=rounds, k_keep=self.cfg.k)
        dd = np.asarray(td, dtype=np.float64)[:, :k]
        ids = np.asarray(ti)[:, :k]
        dd = np.where(dd >= MASK_DIST, np.inf, dd)
        ids = np.where(np.isinf(dd), -1, ids)
        return BatchResult(
            ids=ids.astype(np.int64), dists=dd,
            partitions_scanned=stats["partitions"],
            vectors_scanned=stats["vectors"],
            comparisons=stats["comparisons"],
            nprobe=nprobe, recall_estimate=r_est,
            rounds=n_rounds, round_trace=trace)

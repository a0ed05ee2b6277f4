"""Mesh-sharded Quake serving engine — the TPU adaptation of NUMA-aware
query processing (paper §6, Algorithm 2).

Mapping (see DESIGN.md §3):

  NUMA node                  ->  TPU chip (HBM = local memory)
  round-robin partition      ->  partition j on shard j mod n, local row
  placement                      j div n, of the partition axis sharded
                                 over ("pod","data") (``PartitionLayout``)
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
paper §8.2): ``IndexSnapshot.from_index`` (``core.snapshot``) pads the
base level into a dense ``(P, S_cap, d)`` tensor, placed by the engine's
``layout``.  The planned multi-query entry (``search_batch``)
and the cached, journal-coherent snapshot (``refresh_snapshot``) are the
batched executor's (``core.multiquery.BatchedSearchExecutor``) over the
engine's mesh: the same placement, delta patches, per-shard pack, scan and
merge that ``ServingRuntime`` serves with ``part_shards``.  Three compiled
search paths of the engine's own:

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
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from ..kernels.ref import HIGHEST, MASK_DIST, merge_topk, pairwise_l2_sq
from . import geometry
from .index import QuakeIndex
from .multiquery import BatchedSearchExecutor
from .snapshot import (IndexSnapshot, PartitionLayout,  # noqa: F401
                       SnapshotPatch, merge_shards, partition_layout)

Array = jax.Array


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
        self.batch_axis = config.batch_axis if (
            config.batch_axis in mesh.axis_names) else None
        self.layout = PartitionLayout(mesh, tuple(config.part_axes),
                                      self.batch_axis)
        self.n_part_shards = self.layout.shards
        self.n_batch_shards = self.layout.batch_shards
        self._ex = None     # the batched executor over this engine's mesh

    # ---- sharding specs ----
    def snapshot_spec(self) -> IndexSnapshot:
        pa = P(self.cfg.part_axes)
        return IndexSnapshot(
            data=pa, ids=pa, centroids=pa, sizes=pa, beta_table=P(),
            scales=pa if self.cfg.storage_dtype == "int8" else None)

    def shard_snapshot(self, snap: IndexSnapshot) -> IndexSnapshot:
        """``snap`` on the mesh in the engine's storage dtype.  Rows stay
        where they are: build it with ``IndexSnapshot.from_index(index,
        layout=self.layout)`` for the round-robin placement (the engine's
        own search paths answer with external ids, whatever the row
        order)."""
        lay = self.layout
        data, cents = lay.put(snap.data), lay.put(snap.centroids)
        scales = None
        if self.cfg.storage_dtype == "bf16":
            data = data.astype(jnp.bfloat16)
        elif self.cfg.storage_dtype == "int8":
            # IVF residual SQ8 (paper §8.2): quantize x - c_j, the exact
            # query-centroid term is restored in-kernel
            from ..kernels.scan_topk_indexed import quantize_int8_residual
            data, scales = quantize_int8_residual(data, cents)
        return IndexSnapshot(
            data=data, ids=lay.put(snap.ids),
            centroids=cents, sizes=lay.put(snap.sizes),
            beta_table=lay.put(snap.beta_table, replicated=True),
            scales=scales)

    def executor(self, index: QuakeIndex):
        """The batched executor that keeps this engine's snapshot of
        ``index`` on its mesh (``multiquery.BatchedSearchExecutor`` with
        the engine's ``PartitionLayout``)."""
        if self._ex is None or self._ex.index is not index:
            self._ex = BatchedSearchExecutor(
                index, impl=("pallas" if self.cfg.scan_impl == "union_pallas"
                             else "jnp"),
                storage_dtype=self.cfg.storage_dtype, rounds=self.cfg.rounds,
                layout=self.layout)
        return self._ex

    @property
    def full_rebuilds(self) -> int:
        return self._ex.full_rebuilds if self._ex is not None else 0

    @property
    def delta_refreshes(self) -> int:
        return self._ex.delta_refreshes if self._ex is not None else 0

    def refresh_snapshot(self, index: QuakeIndex) -> IndexSnapshot:
        """Cached device-sharded snapshot of the dynamic index, kept
        coherent through the index's mutation journal by the executor:
        content deltas confined to known partitions patch only the dirty
        rows, each on the shard that owns it; structural changes, int8
        storage, capacity overflow or a trimmed journal rebuild."""
        return self.executor(index).snapshot()

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
        return merge_shards(d_loc, i_loc, k, self.cfg.part_axes)

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
    # planner-driven multi-query entry (the batched executor's)
    # ------------------------------------------------------------------

    def search_batch(self, index: QuakeIndex, queries: np.ndarray,
                     k: Optional[int] = None,
                     nprobe: Optional[int] = None,
                     recall_target: Optional[float] = None,
                     union_cap: Optional[int] = None,
                     rounds: Optional[int] = None):
        """Multi-query search over the sharded snapshot: the batched
        executor's ``search`` over this engine's mesh (the host batch
        planner, per-query APS probe counts when ``nprobe`` is None, the
        multi-round early-exit loop unless ``rounds=1``, a pinned
        ``nprobe`` or a ``union_cap``), each round one per-shard pack,
        scan and merge.  Returns ``multiquery.BatchResult`` (top-``min(k,
        cfg.k)`` columns)."""
        cfg = self.cfg
        k = cfg.k if k is None else min(k, cfg.k)
        return self.executor(index).search(
            queries, k, nprobe=nprobe, recall_target=recall_target,
            union_cap=union_cap if union_cap is not None else cfg.union_cap,
            rounds=cfg.rounds if rounds is None else rounds)

"""The dense device snapshot of a Quake index and where its partitions
live.

``IndexSnapshot`` pads the base level into ``(P, S_cap, d)`` arrays
(copy-on-write semantics, paper §8.2): ``from_index`` stages it whole,
``build_patch`` / ``apply_delta`` replace dirty partition rows in place.
``PartitionLayout`` places the partition axis over a mesh round-robin
(paper §6: partition ``j`` on shard ``j mod n``), ``scatter_rows``
patches rows on the shard that owns them, and ``merge_shards`` is the
cross-shard top-k merge.  The batched executor
(``core.multiquery``) and the sharded engine (``core.distributed``)
both build on these.
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
from . import geometry
from .index import QuakeIndex

Array = jax.Array

# Row-replacement scatters behind apply_delta.  The donated variant
# updates the operand buffer in place (XLA input-output aliasing): the
# refresh cost is O(dirty rows), not O(snapshot) — but the donated array
# is consumed.  Bucket padding in build_patch keeps the set of compiled
# (shape, dtype) specializations small.
_scatter_rows = jax.jit(lambda a, sel, u: a.at[sel].set(u))
_scatter_rows_donated = jax.jit(lambda a, sel, u: a.at[sel].set(u),
                                donate_argnums=(0,))


@dataclass(frozen=True)
class PartitionLayout:
    """Where a snapshot's partitions live (paper §6: partitions are spread
    round-robin over the memory nodes, and each node scans its own).

    Over the ``shards`` devices of ``mesh``'s ``axes``, partition ``j`` of
    a ``p``-row snapshot lives on shard ``j % shards`` in local row
    ``j // shards``: physical row ``(j % shards) * (p // shards) + j //
    shards`` of arrays sharded in blocks along axis 0.  Maintenance
    appends the pieces of every split at the end of the directory, so
    blocks of consecutive ids would put a hot topic's pieces on one
    shard; round-robin deals them out.  Without a mesh, or over one
    device, it is the identity on the default device.

    ``batch_axis`` names a mesh axis that splits a round's query rows
    (the sharded engine's query parallelism); the serving layout has
    none."""
    mesh: Optional[Mesh] = None
    axes: Tuple[str, ...] = ("data",)
    batch_axis: Optional[str] = None

    def _size(self, axes) -> int:
        if self.mesh is None:
            return 1
        sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        return int(np.prod([sizes[a] for a in axes]))

    @functools.cached_property
    def shards(self) -> int:
        return self._size(self.axes)

    @functools.cached_property
    def batch_shards(self) -> int:
        return self._size([self.batch_axis] if self.batch_axis else [])

    @property
    def mapped(self) -> bool:
        """Whether rounds run as one ``shard_map`` over the mesh."""
        return self.shards * self.batch_shards > 1

    def rows(self, j, p: int):
        """Physical rows of partitions ``j`` in a snapshot of ``p`` rows
        (``j`` itself on one shard)."""
        n = self.shards
        if n == 1:
            return j
        j = np.asarray(j)
        return (j % n) * (p // n) + j // n

    @functools.cached_property
    def _scatters(self) -> dict:
        """The jitted per-shard row scatters, by donation (compiled
        programs live as long as the layout)."""
        return {}

    def put(self, x, replicated: bool = False):
        """``x`` on the devices: sharded along its partition axis (or
        ``replicated``); one device keeps today's single-device array."""
        if not self.mapped:
            return jnp.asarray(x)
        spec = P() if replicated else P(self.axes)
        return jax.device_put(x, NamedSharding(self.mesh, spec))


def partition_layout(part_shards: int) -> PartitionLayout:
    """The partition axis sharded over the first ``part_shards`` devices
    in a ``("data",)`` mesh (a mesh of one on one chip)."""
    devs = jax.devices()
    if not 1 <= part_shards <= len(devs):
        raise ValueError(f"part_shards={part_shards} needs 1 to "
                         f"{len(devs)} devices; JAX sees {len(devs)}")
    return PartitionLayout(Mesh(np.array(devs[:part_shards]), ("data",)))


def _sharded_scatter(layout: PartitionLayout, donate: bool):
    fn = layout._scatters.get(donate)
    if fn is None:
        spec = P(layout.axes)
        fn = layout._scatters[donate] = jax.jit(shard_map(
            lambda a, sel, u: a.at[sel].set(u.astype(a.dtype), mode="drop"),
            mesh=layout.mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False), donate_argnums=(0,) if donate else ())
    return fn


def scatter_rows(layout: PartitionLayout, a: Array, rows: np.ndarray,
                 u: np.ndarray, donate: bool) -> Array:
    """``a`` with its physical rows ``rows`` (``PartitionLayout.rows``;
    duplicates carry identical updates) replaced by ``u``, one host row
    each.  Over several shards each shard receives and patches only the
    rows it owns, in place when ``donate``; the unused slots of the
    padded per-shard batch point past the shard and are dropped."""
    if layout.shards == 1:
        set_rows = _scatter_rows_donated if donate else _scatter_rows
        return set_rows(a, jnp.asarray(rows),
                        jnp.asarray(u).astype(a.dtype))
    n = layout.shards
    p_loc = a.shape[0] // n
    rows, first = np.unique(np.asarray(rows), return_index=True)
    u = np.asarray(u)[first]
    shard = rows // p_loc
    counts = np.bincount(shard, minlength=n)
    r = 1
    while r < counts.max():
        r *= 2
    sel = np.full((n, r), p_loc, dtype=np.int32)
    upd = np.zeros((n, r) + u.shape[1:], dtype=u.dtype)
    for i in range(n):
        mine = shard == i
        sel[i, :counts[i]] = rows[mine] % p_loc
        upd[i, :counts[i]] = u[mine]
    return _sharded_scatter(layout, donate)(
        a, layout.put(sel.reshape(-1)),
        layout.put(upd.reshape((n * r,) + u.shape[1:])))


def merge_shards(d: Array, i: Array, k: int, axes) -> Tuple[Array, Array]:
    """Hierarchical top-k merge across the partition shards (the
    coordinator-thread analogue), inside a ``shard_map``: all_gather each
    shard's ascending ``(B, k_loc)`` candidates and re-select ``k``.
    Collective volume: B * n_shards * k_loc * 8 bytes -- negligible next
    to the scan traffic."""
    dg = jax.lax.all_gather(d, axes, axis=1, tiled=True)
    ig = jax.lax.all_gather(i, axes, axis=1, tiled=True)
    vals, sel = jax.lax.top_k(-dg, k)
    return -vals, jnp.take_along_axis(ig, sel, axis=1)


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
                   allow_truncation: bool = False,
                   layout: Optional[PartitionLayout] = None
                   ) -> "IndexSnapshot":
        """Dense snapshot of the base level.

        ``headroom`` pads the slot capacity beyond the current largest
        partition (>1.0 leaves slack so subsequent ``apply_delta`` patches
        rarely force a reshape).  An explicit ``capacity`` smaller than the
        largest partition raises unless ``allow_truncation=True``; with
        truncation allowed the recorded ``sizes`` are clamped to what was
        actually stored, so they always agree with the ``ids >= 0`` mask.

        ``layout`` places the rows: partition ``j`` at ``layout.rows(j,
        P)``, each array sharded over the layout's devices (the partition
        count must then be a multiple of its shards).  Default: the
        identity, on the default device.
        """
        layout = layout or PartitionLayout()
        lvl0 = index.levels[0]
        p_real = lvl0.num_partitions
        p = ((p_real + pad_partitions_to - 1)
             // pad_partitions_to) * pad_partitions_to
        if p % layout.shards:
            raise ValueError(f"{p} partition rows do not split over "
                             f"{layout.shards} shards")
        live = np.zeros(p, dtype=np.int32)
        live[:p_real] = lvl0.sizes()
        if capacity is None:
            s_cap = max(int(math.ceil(int(live.max(initial=0))
                                      * max(headroom, 1.0))), 1)
        else:
            s_cap = capacity
        s_cap = IndexSnapshot.align_capacity(s_cap)
        if int(live.max(initial=0)) > s_cap and not allow_truncation:
            raise ValueError(
                f"IndexSnapshot capacity {s_cap} would truncate a "
                f"partition of size {int(live.max())}; pass "
                "allow_truncation=True to store a lossy snapshot")
        rows = layout.rows(np.arange(p), p)
        d = index.dim
        data = np.zeros((p, s_cap, d), dtype=np.float32)
        ids = np.full((p, s_cap), -1, dtype=np.int32)
        sizes = np.zeros(p, dtype=np.int32)
        for j in range(p_real):
            s = min(int(live[j]), s_cap)
            r = rows[j]
            sizes[r] = s          # recorded size == stored size, always
            data[r, :s] = lvl0.vectors[j][:s]
            ext = lvl0.ids[j][:s]
            if len(ext) and int(ext.max()) > np.iinfo(np.int32).max:
                raise ValueError(
                    "IndexSnapshot stores external ids as int32; id "
                    f"{int(ext.max())} does not fit (partition {j})")
            ids[r, :s] = ext
        cents = np.zeros((p, d), dtype=np.float32)
        cents[rows[:p_real]] = lvl0.centroids
        # padding partitions: park centroids far away so routing never
        # selects them (MASK via sizes==0 also applies)
        if p > p_real:
            cents[rows[p_real:]] = 1e6
        table = index._beta_table     # the index's (fitted) cap model
        return IndexSnapshot(
            data=layout.put(data), ids=layout.put(ids),
            centroids=layout.put(cents), sizes=layout.put(sizes),
            beta_table=layout.put(table, replicated=True))

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
                    donate: bool = False,
                    layout: Optional[PartitionLayout] = None
                    ) -> "IndexSnapshot":
        """Return a new snapshot with the patch rows replaced on device;
        only the patch moves host->device, each row to the shard that
        owns it under ``layout`` (default: one device).

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
        layout = layout or PartitionLayout()
        rows = layout.rows(patch.rows, self.num_partitions)
        return IndexSnapshot(
            data=scatter_rows(layout, self.data, rows, patch.data, donate),
            ids=scatter_rows(layout, self.ids, rows, patch.ids, donate),
            centroids=scatter_rows(layout, self.centroids, rows,
                                   patch.centroids, donate),
            sizes=scatter_rows(layout, self.sizes, rows, patch.sizes,
                               donate),
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

"""quake-ann: the paper's own serving configuration as a first-class arch.

An MSTURING100M-scale snapshot (1.6e8 padded slots, d=128) sharded over the
partition axes, with four shape cells:

  * serve_fixed_1k    — 1024 queries, static nprobe (baseline engine)
  * serve_adaptive_1k — 1024 queries, APS rounds (the paper's contribution)
  * bulk_brute_8k     — 8192 queries, exact multi-query scan
  * maint_assign_1m   — maintenance hot op: route 1M inserted vectors to
                        partitions (fused distance+argmin)

These cells are what the §Perf hillclimb of the paper's own technique
iterates on.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.distributed import EngineConfig, ShardedQuakeEngine
from ..core.snapshot import IndexSnapshot
from ..kernels import ref
from .base import SDS, ArchSpec, Lowering, dp_axes_for, register

FULL = dict(p=16384, s_cap=12288, d=128, k=100)
SMOKE = dict(p=64, s_cap=64, d=32, k=10)

QUAKE_SHAPES = {
    "serve_fixed_1k": dict(kind="fixed", batch=1024, nprobe=64),
    "serve_adaptive_1k": dict(kind="adaptive", batch=1024),
    "bulk_brute_8k": dict(kind="brute", batch=8192),
    "maint_assign_1m": dict(kind="assign", n=1_000_000),
}
QUAKE_SMOKE_SHAPES = {
    "serve_fixed_1k": dict(kind="fixed", batch=16, nprobe=4),
    "serve_adaptive_1k": dict(kind="adaptive", batch=16),
    "bulk_brute_8k": dict(kind="brute", batch=32),
    "maint_assign_1m": dict(kind="assign", n=4096),
}


def _snapshot_sds(dims, n_shards: int, storage: str = "f32"
                  ) -> IndexSnapshot:
    p = -(-dims["p"] // n_shards) * n_shards
    dt = {"f32": jnp.float32, "bf16": jnp.bfloat16,
          "int8": jnp.int8}[storage]
    return IndexSnapshot(
        data=SDS((p, dims["s_cap"], dims["d"]), dt),
        ids=SDS((p, dims["s_cap"]), jnp.int32),
        centroids=SDS((p, dims["d"]), jnp.float32),
        sizes=SDS((p,), jnp.int32),
        beta_table=SDS((1024,), jnp.float32),
        scales=(SDS((p, dims["s_cap"]), jnp.float32)
                if storage == "int8" else None))


def build_quake(shape: str, mesh, smoke: bool = False,
                engine_overrides: dict | None = None) -> Lowering:
    dims = SMOKE if smoke else FULL
    sh = (QUAKE_SMOKE_SHAPES if smoke else QUAKE_SHAPES)[shape]
    dp = dp_axes_for(mesh)

    if sh["kind"] == "assign":
        # maintenance routing: points sharded over dp, centroids replicated
        from ..kernels.ref import kmeans_assign_ref
        pts = SDS((sh["n"], dims["d"]), jnp.float32)
        cents = SDS((dims["p"], dims["d"]), jnp.float32)
        return Lowering(
        mesh=mesh, fn=kmeans_assign_ref, args=(pts, cents),
            in_shardings=(NamedSharding(mesh, P(dp, None)),
                          NamedSharding(mesh, P())),
            description=f"quake maintenance assign n={sh['n']}")

    cfg = EngineConfig(metric="l2", k=dims["k"],
                       nprobe=sh.get("nprobe", 16),
                       part_axes=dp, batch_axis="model",
                       **(engine_overrides or {}))
    eng = ShardedQuakeEngine(mesh, cfg)
    snap = _snapshot_sds(dims, eng.n_part_shards, cfg.storage_dtype)
    b = sh["batch"]
    q = SDS((b, dims["d"]), jnp.float32)
    qsh = NamedSharding(mesh, eng.query_spec())
    snap_sh = jax.tree.map(
        lambda s: NamedSharding(mesh, s), eng.snapshot_spec(),
        is_leaf=lambda x: isinstance(x, P))
    return Lowering(
        mesh=mesh, fn=eng.mapped_fn(sh["kind"]), args=(q, snap),
                    in_shardings=(qsh, snap_sh),
                    description=f"quake {sh['kind']} B={b} "
                                f"P={snap.data.shape[0]}")


register(ArchSpec(
    name="quake-ann", family="ann",
    source="Quake (this paper)", shapes=tuple(QUAKE_SHAPES),
    model_config=lambda: dict(FULL),
    smoke_config=lambda: dict(SMOKE),
    build=build_quake,
    notes="the paper's own serving engine on the production mesh"))

"""Paper Figure 6: scaling query processing across devices.

The paper scales threads across NUMA nodes; the TPU adaptation scales
devices across the mesh.  This container has ONE physical core, so
wall-clock cannot show real scaling — we report the *structural* scaling
(per-device scan bytes, which is what saturates HBM on real hardware) from
subprocess runs at 1/2/4/8 virtual devices, for both the NUMA-aware layout
(partitions sharded; each device scans only residents) and the unaware one
(snapshot replicated; batch-sharded only), plus wall time for reference.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from .common import Rows

SCRIPT = textwrap.dedent("""
    import json, sys, time
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core import (EngineConfig, IndexSnapshot, QuakeIndex,
                            ShardedQuakeEngine)
    from repro.data import datasets

    ndev = len(jax.devices())
    ds = datasets.clustered(20000, 32, n_clusters=32, seed=0)
    idx = QuakeIndex.build(ds.vectors, num_partitions=64, kmeans_iters=3)
    mesh = Mesh(np.array(jax.devices()).reshape(ndev, 1), ("data", "model"))

    out = {}
    for mode in ("numa", "no_numa"):
        part_axes = ("data",) if mode == "numa" else ()
        if mode == "numa":
            eng = ShardedQuakeEngine(mesh, EngineConfig(
                k=10, nprobe=16, part_axes=("data",), batch_axis="model"))
            snap = IndexSnapshot.from_index(
                idx, pad_partitions_to=eng.n_part_shards, layout=eng.layout)
        else:
            # unaware: snapshot replicated; only the batch splits
            eng = ShardedQuakeEngine(mesh, EngineConfig(
                k=10, nprobe=16, part_axes=(), batch_axis="data"))
            snap = IndexSnapshot.from_index(idx, pad_partitions_to=1)
        ss = eng.shard_snapshot(snap)
        q = jnp.asarray(datasets.queries_near(ds, 256, seed=1))
        d, i = eng.search_fixed(q, ss)   # warm/compile
        jax.block_until_ready(d)
        t0 = time.perf_counter()
        for _ in range(3):
            d, i = eng.search_fixed(q, ss)
            jax.block_until_ready(d)
        dt = (time.perf_counter() - t0) / 3
        bytes_total = float(snap.data.size * 4) * (16 / snap.num_partitions)
        out[mode] = {
            "wall_s": dt,
            "scan_bytes_per_device": bytes_total / (
                ndev if mode == "numa" else 1),
        }
    print("RESULT" + json.dumps(out))
""")


def run(device_counts=(1, 2, 4, 8)):
    rows = Rows()
    env_base = dict(os.environ)
    env_base["PYTHONPATH"] = os.path.join(
        os.path.dirname(__file__), "..", "src")
    for nd in device_counts:
        env = dict(env_base)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={nd}"
        # virtual CPU devices; the parent may hold the chip
        env["JAX_PLATFORMS"] = "cpu"
        p = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                           capture_output=True, text=True, timeout=900)
        if p.returncode != 0:
            print(p.stderr[-2000:])
            raise RuntimeError(f"scaling run failed at {nd} devices")
        data = json.loads(p.stdout.split("RESULT")[1])
        rows.add(devices=nd,
                 numa_scan_mb_per_dev=data["numa"][
                     "scan_bytes_per_device"] / 1e6,
                 numa_wall_ms=data["numa"]["wall_s"] * 1e3,
                 flat_scan_mb_per_dev=data["no_numa"][
                     "scan_bytes_per_device"] / 1e6,
                 flat_wall_ms=data["no_numa"]["wall_s"] * 1e3)
    rows.print_table("Figure 6 analogue: device scaling "
                     "(structural; 1 physical core)")
    return rows


if __name__ == "__main__":
    run()

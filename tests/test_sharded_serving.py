"""``ServingRuntime`` over a partition-sharded snapshot (``part_shards``)
against the same runtime on one device and against brute force.

The main pytest process keeps the single real device; the four-device
checks run in a subprocess with four virtual host devices, as in
``tests/test_distributed.py``.  The script builds seeded random corpora
(IP and L2), serves them with ``part_shards=4`` and ``part_shards=1``
(fixed plans: no early exit) and checks:

* the four-shard top-k id sets equal the one-shard ones exactly, and
  the distances agree to float32 rounding (the coalescing-determinism
  contract does not depend on the shards);
* the witness (every partition) equals brute force in ``jnp`` f32 at
  ``HIGHEST``, for the runtime and for fixed-``nprobe`` and APS searches
  of the executor;
* after inserts, deletes and one maintenance pass the four-shard results
  still equal brute force over the resident rows (read-your-writes
  through sharded deltas and a rebuild);
* partitions are placed round-robin, also after ``split_to_fit``;
* ``scan.shards`` and ``scan.shard_vectors_max`` read what the rounds
  streamed.
"""
import os
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import numpy as np, jax, jax.numpy as jnp
    from repro.core import (QuakeConfig, QuakeIndex, ServingConfig,
                            ServingRuntime)
    from repro.kernels.ref import HIGHEST

    assert len(jax.devices()) == 4

    def corpus(metric, n, d, seed):
        rng = np.random.default_rng(seed)
        c = rng.normal(size=(24, d)) * 2.0
        x = c[rng.integers(0, 24, n)] + rng.normal(size=(n, d))
        if metric == "ip":
            x /= np.linalg.norm(x, axis=1, keepdims=True)
        return x.astype(np.float32), rng

    def brute(x, ids, q, k, metric):
        xj, qj = jnp.asarray(x), jnp.asarray(q)
        s = jnp.matmul(qj, xj.T, precision=HIGHEST)
        if metric == "l2":
            s = 2.0 * s - jnp.sum(xj * xj, axis=1)[None, :]
        top = np.asarray(jax.lax.top_k(s, k)[1])
        return [set(ids[r].tolist()) for r in top]

    def served(rt, q):
        qids = [rt.submit_query(v) for v in q]
        rt.drain()
        res = [rt.result(i) for i in qids]
        assert all(r.status == "OK" for r in res)
        return (np.stack([r.ids for r in res]),
                np.stack([r.dists for r in res]))

    def runtime(x, metric, shards):
        idx = QuakeIndex.build(x, np.arange(len(x)), config=QuakeConfig(
            metric=metric, snapshot_capacity=64))
        rt = ServingRuntime(idx, ServingConfig(
            k=10, scan_backend="device", part_shards=shards, ticker=False,
            flush_size=8, maint_min_ops=1_000_000_000, maint_max_ops=None))
        rt.stage()
        return rt

    def placed_round_robin(ex):
        lay, p = ex.layout, ex._snap.num_partitions
        lvl0 = ex.index.levels[0]
        cap = ex._snap.capacity
        rows = lay.rows(np.arange(lvl0.num_partitions), p)
        assert np.array_equal(rows // (p // lay.shards),
                              np.arange(lvl0.num_partitions) % lay.shards)
        flat = ex._flat_ids.reshape(p, cap)
        dev_ids = np.asarray(ex._snap.ids)
        for j in range(lvl0.num_partitions):
            s = len(lvl0.ids[j])
            assert ex._sizes[rows[j]] == s
            assert np.array_equal(np.sort(flat[rows[j], :s]),
                                  np.sort(lvl0.ids[j]))
            assert np.array_equal(dev_ids[rows[j]], flat[rows[j]])
        per_dev = [sh.data.shape[0] for sh in ex._snap.data.addressable_shards]
        assert per_dev == [p // lay.shards] * lay.shards, per_dev

    for metric, n, d in (("ip", 3000, 32), ("l2", 2500, 64)):
        x, rng = corpus(metric, n, d, 7 if metric == "ip" else 8)
        q = x[rng.choice(n, 24)] + 0.05 * rng.normal(size=(24, d)).astype(
            np.float32)
        one, four = runtime(x, metric, 1), runtime(x, metric, 4)
        ex = four.executor
        assert ex.shards == 4 and one.executor.shards == 1
        # the split to fit appended pieces: they too are dealt round-robin
        assert four.index.num_partitions > int(round(np.sqrt(n)))
        placed_round_robin(ex)
        # served (fixed plans, APS): four shards answer as one does
        i1, d1 = served(one, q)
        ms0 = four.metrics_snapshot()
        i4, d4 = served(four, q)
        ms1 = four.metrics_snapshot()
        assert [set(a) for a in i1] == [set(b) for b in i4]
        # one shard runs the single-device programs: no sharded round
        assert not one.executor.layout.mapped
        assert one.executor._round_fns == {} and ex._round_fns
        # float32 rounding of the terms each distance sums
        scale = float((q * q).sum(1).max() + (x * x).sum(1).max())
        np.testing.assert_allclose(d1, d4, rtol=0, atol=1e-6 * scale)
        # the counters read what the rounds streamed
        sch = four.scheduler
        assert ms1["scan.shards"] == 4
        lvl0 = four.index.levels[0]
        vmax = 0
        for kept in sch.round_streams:
            sizes = np.asarray([len(lvl0.ids[j]) for j in kept])
            vmax += int(np.bincount(kept % 4, weights=sizes,
                                    minlength=4).max())
        assert ms1["scan.shard_vectors_max"] == vmax
        assert ms1["scheduler.vectors_streamed"] == sch.vectors_streamed
        assert 0 < ms1["scan.shard_vectors_max"] \
            - ms0.get("scan.shard_vectors_max", 0) <= sch.vectors_streamed
        # the witness, a fixed nprobe and APS searches of the executor
        ids = np.arange(n)
        truth = brute(x, ids, q, 10, metric)
        w = ex.search(q, 10, nprobe=four.index.num_partitions)
        assert [set(r) for r in w.ids] == truth
        for kw in ({"nprobe": 6}, {}):
            a = one.executor.search(q, 10, **kw)
            b = ex.search(q, 10, **kw)
            assert [set(r) for r in a.ids] == [set(r) for r in b.ids], kw
            assert a.vectors_scanned == b.vectors_scanned
        # writes: inserts and deletes patch rows on their shards, then a
        # maintenance pass rebuilds
        # (few enough dirty partitions for a delta, not a rebuild)
        new = (x[:16] + 0.01 * rng.normal(size=(16, d))).astype(np.float32)
        if metric == "ip":
            new /= np.linalg.norm(new, axis=1, keepdims=True)
        new_ids = np.arange(10_000, 10_016)
        dead = rng.choice(n, 12, replace=False)
        rebuilds = ex.full_rebuilds
        four.submit_insert(new, new_ids)
        four.submit_delete(dead)
        alive = np.setdiff1d(ids, dead)
        xs = np.concatenate([x[alive], new])
        all_ids = np.concatenate([alive, new_ids])
        qw = np.concatenate([new[:8], q[:8]])
        truth = brute(xs, all_ids, qw, 10, metric)
        iw, _ = served(four, qw)
        assert ex.delta_refreshes >= 1 and ex.full_rebuilds == rebuilds
        w = ex.search(qw, 10, nprobe=four.index.num_partitions)
        assert [set(r) for r in w.ids] == truth
        # the served path itself reads its writes on every shard
        assert all(set(iw[r]) >= {int(new_ids[r])} for r in range(8))
        four.maybe_maintain(force=True)
        qm = q[8:16]
        w = ex.search(qm, 10, nprobe=four.index.num_partitions)
        assert ex.full_rebuilds > rebuilds
        assert [set(r) for r in w.ids] == brute(xs, all_ids, qm, 10, metric)
        placed_round_robin(ex)
        im, _ = served(four, qm)
        one.close(); four.close()
    print("SHARDED_OK")
""")


def test_sharded_serving_subprocess():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARDED_OK" in out.stdout

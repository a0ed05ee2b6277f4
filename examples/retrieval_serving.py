"""Candidate retrieval through the Quake index (the paper's use case).

    PYTHONPATH=src python examples/retrieval_serving.py

End-to-end recsys retrieval path:
  1. a two-tower model (assigned arch `two-tower-retrieval`, scaled down)
     encodes users and a 60k-item corpus into a shared inner-product space,
  2. the item embeddings are indexed by Quake (MIPS metric),
  3. user queries are served three ways and compared:
       brute     — exact batched GEMM over all items (retrieval_cand path)
       quake     — host QuakeIndex with APS at a 0.9 recall target
       engine    — compiled ShardedQuakeEngine (the TPU-form hot path:
                   padded partitions + fixed-nprobe scan under jit)
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core import (EngineConfig, IndexSnapshot, QuakeConfig, QuakeIndex,
                        ShardedQuakeEngine)
from repro.models import recsys


def main():
    rng = np.random.default_rng(0)
    cfg = recsys.TwoTowerConfig(user_vocab=20_000, item_vocab=60_000,
                                embed_dim=32, tower_mlp=(64, 32),
                                hist_len=16)
    params = recsys.twotower_init(jax.random.PRNGKey(0), cfg)

    # --- encode the item corpus (what a nightly batch job would do) -------
    item_ids = jnp.arange(cfg.item_vocab)
    items = np.asarray(jax.jit(
        lambda p, i: recsys.item_repr(p, i, cfg))(params, item_ids))
    print(f"encoded {items.shape[0]} items, dim={items.shape[1]}")

    # --- encode a user query batch ----------------------------------------
    B = 256
    batch = {"history": jnp.asarray(
                 rng.integers(0, cfg.user_vocab, (B, cfg.hist_len))),
             "history_mask": jnp.ones((B, cfg.hist_len), bool)}
    users = np.asarray(jax.jit(
        lambda p, b: recsys.user_repr(p, b, cfg))(params, batch))

    # --- exact baseline: one GEMM (the retrieval_cand dry-run cell) -------
    k = 10
    t0 = time.perf_counter()
    scores = users @ items.T
    gt = np.argsort(-scores, axis=1)[:, :k]
    t_brute = (time.perf_counter() - t0) / B * 1e6

    # --- Quake host index with APS ----------------------------------------
    idx = QuakeIndex.build(items, config=QuakeConfig(metric="ip"))
    t0 = time.perf_counter()
    recs, scanned = [], []
    for i in range(B):
        r = idx.search(users[i], k, recall_target=0.9)
        recs.append(len(set(r.ids.tolist()) & set(gt[i].tolist())) / k)
        scanned.append(r.vectors_scanned)
    t_quake = (time.perf_counter() - t0) / B * 1e6
    print(f"\nbrute : {t_brute:7.0f} us/query  recall=1.000  "
          f"scanned={items.shape[0]}")
    print(f"quake : {t_quake:7.0f} us/query  recall={np.mean(recs):.3f}  "
          f"scanned={np.mean(scanned):.0f}  "
          f"({items.shape[0]/np.mean(scanned):.0f}x fewer)")

    # --- compiled engine (the sharded TPU path, single host device) -------
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1),
                ("pod", "data", "model"))
    eng = ShardedQuakeEngine(mesh, EngineConfig(
        k=k, nprobe=16, recall_target=0.9, part_axes=("pod", "data")))
    snap = eng.shard_snapshot(
        IndexSnapshot.from_index(idx, layout=eng.layout))
    qs = eng.pad_queries(jnp.asarray(users))
    d_e, i_e, r_est, nprobe = eng.search_adaptive(qs, snap)   # compile
    t0 = time.perf_counter()
    d_e, i_e, r_est, nprobe = eng.search_adaptive(qs, snap)
    jax.block_until_ready(d_e)
    t_eng = (time.perf_counter() - t0) / B * 1e6
    rec_e = np.mean([len(set(np.asarray(i_e[r]).tolist())
                         & set(gt[r].tolist())) / k for r in range(B)])
    print(f"engine: {t_eng:7.0f} us/query  recall={rec_e:.3f}  "
          f"(jit, batched, APS rounds, mean nprobe="
          f"{float(np.mean(np.asarray(nprobe))):.1f})")

    # --- int8 residual-quantized engine (paper §8.2; 4x less scan HBM) ----
    eng8 = ShardedQuakeEngine(mesh, EngineConfig(
        k=k, nprobe=24, part_axes=("pod", "data"),
        scan_impl="union_pallas", storage_dtype="int8"))
    ss8 = eng8.shard_snapshot(
        IndexSnapshot.from_index(idx, layout=eng8.layout))
    d_8, i_8 = eng8.search_fixed(qs, ss8)
    rec_8 = np.mean([len(set(np.asarray(i_8[r]).tolist())
                         & set(gt[r].tolist())) / k for r in range(B)])
    print(f"int8  :      —  us/query  recall={rec_8:.3f}  "
          f"(IVF-residual SQ8 codes, 4x less scan traffic)")


if __name__ == "__main__":
    main()

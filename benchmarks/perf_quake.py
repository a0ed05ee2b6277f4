"""§Perf hillclimb driver for the paper's own cells (quake-ann serve).

Lowers ``serve_fixed_1k`` / ``serve_adaptive_1k`` on the single-pod
production mesh under each scan implementation and reports the three
roofline terms.  Must run in a fresh process (device-count flag):

    PYTHONPATH=src python -m benchmarks.perf_quake [--shape serve_fixed_1k]

Ladder:
  gather        paper-faithful XLA baseline (per-query gather + einsum)
  union_jnp     + batch dedupe (paper §7.4 multi-query policy per shard)
  union_pallas  + scalar-prefetch Pallas kernel (beyond-paper; each block
                streams HBM->VMEM once).  The CPU dry-run lowers the
                interpret-mode kernel (slice-loop HLO); the TPU-native
                traffic model (U*S*d*bytes, exact) is printed alongside.
  union_skew4   union_pallas with union_cap = B*n/4 — the paper's read-skew
                regime (Fig. 1a: hot partitions shared across the batch).
"""
import os

# a CPU study on 512 virtual devices: it must never take the chip
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

import argparse
import time


def _merge_results(out_path: str, key: str, value) -> None:
    """Merge one cell into the shared results JSON (see common.py; imported
    lazily so this module can keep setting XLA_FLAGS before jax loads)."""
    from benchmarks.common import merge_results
    merge_results(out_path, key, value)


def run(shape: str, variants=None, out_path="results/perf_quake.json"):
    import jax
    from repro.configs.quake_arch import build_quake, FULL, QUAKE_SHAPES
    from repro.launch.mesh import make_production_mesh
    from repro.roofline.analysis import (DRYRUN_TARGET_KIND,
                                         analyze_compiled, peaks)

    print(f"roofline terms below are compile-time estimates from a CPU "
          f"compile against {DRYRUN_TARGET_KIND} peaks; not measured")
    mesh = make_production_mesh()
    sh = QUAKE_SHAPES[shape]
    b = sh.get("batch", 1024)
    n_shards = 16
    b_loc = b // 16                      # model-axis query shards
    n_loc = max(1, -(-sh.get("nprobe", 16) // n_shards))
    full_union = b_loc * (n_loc if shape == "serve_fixed_1k" else 2)

    all_variants = {
        "gather": {},
        "union_jnp": {"scan_impl": "union_jnp"},
        "union_pallas": {"scan_impl": "union_pallas"},
        "union_skew4": {"scan_impl": "union_pallas",
                        "union_cap": max(full_union // 4, 1)},
        "union_bf16": {"scan_impl": "union_pallas",
                       "storage_dtype": "bf16"},
        "bf16_skew4": {"scan_impl": "union_pallas",
                       "storage_dtype": "bf16",
                       "union_cap": max(full_union // 4, 1)},
        "union_int8": {"scan_impl": "union_pallas",
                       "storage_dtype": "int8"},
        "int8_skew4": {"scan_impl": "union_pallas",
                       "storage_dtype": "int8",
                       "union_cap": max(full_union // 4, 1)},
    }
    chosen = {k: v for k, v in all_variants.items()
              if variants is None or k in variants}

    results = {}
    for name, ov in chosen.items():
        lw = build_quake(shape, mesh, engine_overrides=ov)
        t0 = time.perf_counter()
        lowered = lw.lower()
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()
        r = analyze_compiled(compiled, mesh, device_kind=DRYRUN_TARGET_KIND,
                             arch="quake-ann", shape=shape)
        r["lower_s"] = round(t1 - t0, 1)
        r["compile_s"] = round(t2 - t1, 1)
        r["variant"] = name
        # TPU-native analytic traffic for the pallas kernel cell: the
        # interpret-mode HLO loops slice blocks through XLA buffers; on
        # TPU/Mosaic each selected block streams HBM->VMEM exactly once.
        if "pallas" in ov.get("scan_impl", ""):
            u = ov.get("union_cap", full_union)
            s_cap, d = FULL["s_cap"], FULL["d"]
            sd = ov.get("storage_dtype", "f32")
            vb = {"f32": 4, "bf16": 2, "int8": 1}[sd]
            per_slot_meta = 8 if sd == "int8" else 4   # scales + aux | aux
            native = (u * s_cap * d * vb           # selected blocks, once
                      + u * s_cap * per_slot_meta  # aux (+ dequant scales)
                      + b_loc * (d + 2 * u) * 4    # queries + qmask + qc
                      + 2 * b_loc * 128 * 8)       # top-k out
            r["tpu_native_bytes_gb"] = round(native / 1e9, 4)
            r["tpu_native_t_memory_ms"] = round(
                native / peaks(DRYRUN_TARGET_KIND)["hbm_bw"] * 1e3, 4)
        results[name] = r
        print(f"{name:>13}: t_comp {r['t_compute_ms']:.3f}ms  "
              f"t_mem {r['t_memory_ms']:.3f}ms  "
              f"t_coll {r['t_collective_ms']:.3f}ms  "
              f"dom={r['dominant']}"
              + (f"  [TPU-native mem {r['tpu_native_t_memory_ms']:.3f}ms]"
                 if "tpu_native_t_memory_ms" in r else ""))

    _merge_results(out_path, shape, results)
    return results


def run_multiquery(out_path="results/perf_quake.json", n=20_000, b=256,
                   nprobe=12, k=10):
    """Batched-vs-single QPS + vectors-scanned for the device-resident
    multi-query executor (paper §7.4) — the host-scale companion to the
    lowered serve cells above.  Runs on the current host backend (the
    packed scan is the same ``scan_topk_indexed`` primitive the sharded
    engine uses per shard)."""
    import numpy as np
    from repro.core.multiquery import batch_search, per_query_search
    from repro.data import datasets
    from benchmarks.common import build_index, sift_like

    ds = sift_like(n, 32, 0)
    idx = build_index(ds)
    q = datasets.queries_near(ds, b, seed=6)
    batch_search(idx, q, k, nprobe=nprobe)          # warm the (B, U) shape
    t0 = time.perf_counter()
    rb = batch_search(idx, q, k, nprobe=nprobe)
    t_b = time.perf_counter() - t0
    b_per = min(b, 64)
    per_query_search(idx, q[:2], k, nprobe=nprobe)  # warm the B=1 shape
    t0 = time.perf_counter()
    rp = per_query_search(idx, q[:b_per], k, nprobe=nprobe)
    t_p = (time.perf_counter() - t0) / b_per * b
    r = {"batch": b, "nprobe": nprobe,
         "qps_batched": round(b / t_b, 1),
         "qps_single": round(b / t_p, 1),
         "partitions_scanned": rb.partitions_scanned,
         "partitions_single": int(rp.partitions_scanned / b_per * b),
         "vectors_scanned": rb.vectors_scanned,
         "vectors_single": int(rp.vectors_scanned / b_per * b),
         "scan_amortization": round(
             rp.vectors_scanned / b_per * b / max(rb.vectors_scanned, 1), 2)}
    print(f"multiquery B={b}: batched {r['qps_batched']} qps / "
          f"{r['vectors_scanned']} vec streamed  vs  single "
          f"{r['qps_single']} qps / {r['vectors_single']} vec "
          f"({r['scan_amortization']}x less scan traffic)")
    _merge_results(out_path, "multiquery", r)
    return r


def run_streaming(out_path="results/perf_quake.json", n=100_000,
                  insert_batch=256, steps=5):
    """Streaming-update cell (paper §8.2 update-latency claim): per-batch
    snapshot refresh cost, full rebuild vs journal-driven delta patch.
    Delta refresh must be >=5x cheaper than the full rebuild at N=100k —
    and scale with the dirty-partition count, not the index size."""
    from benchmarks.bench_streaming import run as run_stream

    r = run_stream(n=n, insert_batch=insert_batch, steps=steps)
    # steady-state rows only (a first-seen patch shape pays one compile)
    print(f"streaming N={n}: delta refresh {r['speedup']}x cheaper than "
          f"full rebuild ({r['t_delta_refresh_ms_median']}ms vs "
          f"{r['t_full_rebuild_ms']}ms)")
    _merge_results(out_path, "streaming", r)
    return r


def run_serving(out_path="results/perf_quake.json", n=20_000, n_ops=24,
                queries_per_op=256):
    """Serving-runtime cell (the online system of paper §3): the
    micro-batching / riding / caching / drift-maintenance runtime vs the
    per-op replay baseline on the generator's skewed read-write mix.
    The runtime must hold >=1.5x baseline query throughput within a
    point of recall (locally ~3x at smoke N=20k)."""
    from benchmarks.bench_serving import run as run_serve

    r = run_serve(n=n, n_ops=n_ops, queries_per_op=queries_per_op,
                  out_path=out_path)
    print(f"serving N={n}: runtime {r['throughput_ratio']}x baseline "
          f"qps at recall gap {r['recall_gap']}")
    return r


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="serve_fixed_1k",
                    choices=["serve_fixed_1k", "serve_adaptive_1k"])
    ap.add_argument("--variants", default=None,
                    help="comma list (default: all)")
    ap.add_argument("--multiquery", action="store_true",
                    help="batched-vs-single executor comparison instead of "
                         "the lowered serve cells")
    ap.add_argument("--streaming", action="store_true",
                    help="streaming-update cell: full-rebuild vs delta-"
                         "refresh snapshot cost under an insert stream")
    ap.add_argument("--serving", action="store_true",
                    help="serving-runtime cell: ServingRuntime vs the "
                         "per-op replay baseline on the skewed mix")
    args = ap.parse_args()
    if args.multiquery:
        run_multiquery()
    elif args.streaming:
        run_streaming()
    elif args.serving:
        run_serving()
    else:
        run(args.shape,
            args.variants.split(",") if args.variants else None)

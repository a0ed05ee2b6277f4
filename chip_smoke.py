"""Smoke test of Quake on a TPU: the served path at real size, on one chip.

    python chip_smoke.py              # one chip: the served path
    python chip_smoke.py --chips 4    # four chips: the partition-sharded engine

One chip drives the normal entry point, ``repro.launch.serve.replay_runtime``:
``QuakeIndex.build`` of a Wikipedia-style corpus at d=768 (IP metric; N is
cut below the 1M asked for to keep the run inside its time limit, and the
run prints the cut), one maintenance pass, then ``ServingRuntime``
with the device scan backend and the Pallas kernels replaying the
generator's operations: its month-1 insert burst of new topics (partitions
it grows past the snapshot's slots are split to fit, and the snapshot takes
the burst as a delta refresh) and 256 queries in flushes of 64.  On the
same snapshot a fixed plan over every partition must then be exact: the
witness that separates the scan path from APS's choice of partitions.
Before all that it checks the indexed scan kernel on the chip against the
plain ``jnp`` reference at highest matmul precision.

``--chips 4`` runs only ``ShardedQuakeEngine`` (scan_impl="union_pallas")
over a ("data", "model") = (4, 1) mesh on the same corpus, and the same
engine on one chip for comparison.

The script fails (non-zero exit, no ``ok`` line) when JAX finds no TPU, when
any check fails, and when the ``repro`` package is not next to it.  The last
line of a passing run is ``{"ok": true, "device": {...}}``.  The persistent
compilation cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to
``.jax_cache`` in the checkout.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# --- the configuration (Wikipedia-style corpus and queries, paper §7.1) ---
DIM = 768            # assumed width: PAPER.md does not state it
RESIDENT_ASKED = 1_000_000   # vectors resident after the build, as asked
N_TOTAL = 450_000    # the cut: pages in the corpus (389,406 resident
                     # after the build, 450,000 after the month-1 burst),
                     # set by the run's time limit: the maintenance pass
                     # after the build grows ~N^1.6 (PERF.md)
MONTHS = 2           # two months of queries (the generator's trace)
QUERIES_PER_MONTH = 128
FLUSH = 64
K = 10
RECALL_TARGET = 0.9
RECALL_SLACK = 0.05  # mean recall@10 must reach RECALL_TARGET - this
EXHAUSTIVE_MIN = 0.99  # a scan of every partition is exact but for ties
SEED = 0
HBM_SHARE_MAX = 0.75   # padded snapshot over this share of HBM: cut N
SLOT_CAPACITY = 1024   # slots per partition in the device snapshot,
                       # ~2.7x the mean partition after the maintenance
                       # pass; larger partitions are split to 1024 / 1.5

T0 = time.time()


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(f"[{time.time() - T0:7.1f}s] {msg}", flush=True)


def kernel_check(dim: int, seed: int = SEED) -> None:
    """The indexed scan kernel on the chip against ``ref.scan_selected_ref``
    at highest precision, at the served width and a snapshot-like block
    (compiling alone cannot show a wrong rotation direction, mask or
    contract precision)."""
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref

    rng = np.random.default_rng(seed)
    p, s, b, u = 32, 2048, FLUSH, 16
    data = jnp.asarray(rng.normal(size=(p, s, dim)), jnp.float32)
    valid = jnp.asarray(rng.random((p, s)) < 0.9)
    sel = jnp.asarray(rng.choice(p, u, replace=False), jnp.int32)
    qmask = jnp.asarray(rng.random((b, u)) < 0.6)
    q = jnp.asarray(rng.normal(size=(b, dim)), jnp.float32)
    for metric in ("ip", "l2"):
        for k in (10, 100):
            # the reference asks for HIGHEST itself; the kernel runs as the
            # served path calls it, with no precision context around it
            d_ref, i_ref = ref.scan_selected_ref(q, data, valid, sel, qmask,
                                                 k, metric)
            d_pal, i_pal = ops.scan_selected_topk(
                q, data, valid, sel, qmask, k, metric=metric, impl="pallas")
            d_ref, i_ref, d_pal, i_pal = (np.asarray(a) for a in
                                          (d_ref, i_ref, d_pal, i_pal))
            # ids as sets: two keys within rounding of each other may
            # trade slots; their distances then agree slot by slot anyway
            same_ids = float(np.mean([len(set(a) & set(r)) / k
                                      for a, r in zip(i_pal, i_ref)]))
            err = float(np.max(np.abs(d_pal - d_ref)
                               / (1.0 + np.abs(d_ref))))
            ascending = bool(np.all(np.diff(d_pal, axis=1) >= 0))
            log(f"kernel check {metric} k={k}: ids shared {same_ids:.4f}, "
                f"max rel dist err {err:.2e}, ascending {ascending}")
            check(same_ids >= 0.999 and err <= 1e-4 and ascending,
                  f"scan_selected_topk(pallas) disagrees with "
                  f"scan_selected_ref ({metric}, k={k})")


def round_scan_has_kernel(last_scan: dict) -> bool:
    """Whether the round scan program holds the Mosaic kernel (and not an
    XLA fallback), lowered at the operand shapes and arguments of the
    served path's last round scan."""
    from repro.kernels import ops

    check(last_scan is not None and last_scan["impl"] == "pallas",
          f"the served path's round scan did not take the Pallas kernel: "
          f"{last_scan and last_scan['impl']}")
    ops_ = last_scan["operands"]
    u, s = ops_[3].shape[0], ops_[1].shape[1]
    lowered = ops._scan_selected_pallas_padded.lower(
        *ops_, min(last_scan["k"], u * s), last_scan["metric"], 128, 512)
    return "tpu_custom_call" in lowered.compile().as_text()


def make_workload(n_total: int, dim: int):
    """The generator's corpus and operations as it makes them: the build
    takes the pages that exist in month 0; month 1 inserts the pages of
    the topics born then (a burst of new topics, each in a region of the
    embedding space that has no partition of its own yet) and queries;
    month 2 queries."""
    import numpy as np
    from repro.data import wikipedia
    t0 = time.time()
    wl = wikipedia.wikipedia_workload(
        n_total=n_total, dim=dim, months=MONTHS,
        queries_per_month=QUERIES_PER_MONTH, seed=SEED)
    kinds = [op.kind for op in wl.operations]
    check(kinds == ["insert", "query", "query"],
          f"unexpected operation mix {kinds}")
    burst = wl.operations[0].ids
    topics = wl.dataset.cluster_of
    log(f"workload: {len(wl.dataset.vectors)} pages, "
        f"{len(wl.initial_ids)} resident after the build, an insert burst "
        f"of {len(burst)} pages in {len(np.unique(topics[burst]))} new "
        f"topics, {MONTHS} x {QUERIES_PER_MONTH} queries; d={dim}, IP "
        f"metric ({time.time() - t0:.1f}s to generate)")
    return wl


def run_served(n_total: int = N_TOTAL, dim: int = DIM,
               hbm_bytes: float = 16e9,
               min_resident: int = 350_000) -> None:
    """One chip: build, one maintenance pass, and serve through
    ``replay_runtime`` with the device backend and the Pallas kernels;
    then, on the same device snapshot, a fixed plan over every partition
    as the witness that the scan path itself is exact."""
    import numpy as np
    from repro.core import QuakeConfig, ServingConfig
    from repro.core.serving import STATUS_OK
    from repro.data.workload import IncrementalGroundTruth
    from repro.launch.serve import replay_runtime

    wl = make_workload(n_total, dim)
    n_built = len(wl.initial_ids)
    queries = np.concatenate([op.queries for op in wl.operations
                              if op.kind == "query"])

    def exhaustive(rt) -> dict:
        # every resident vector is a candidate: the scan alone decides
        gt = IncrementalGroundTruth(
            wl.dataset, np.concatenate([wl.initial_ids,
                                        wl.operations[0].ids]))
        truth = gt.topk(queries, K)
        t0 = time.time()
        res = rt.executor.search(queries, K,
                                 nprobe=rt.index.num_partitions)
        dt = time.time() - t0
        rec = float(np.mean([len(set(r.tolist()) & set(g.tolist())) / K
                             for r, g in zip(res.ids, truth)]))
        return {"recall": rec, "nprobe": rt.index.num_partitions,
                "seconds": round(dt, 3),
                "impl": rt.executor.last_scan["impl"]}

    # the snapshot must stay within HBM: its slot capacity is fixed, and
    # partitions that outgrow it (the build's largest, the burst's) are
    # split to fit rather than growing every slot
    cfg = QuakeConfig(metric="ip", recall_target=RECALL_TARGET,
                      snapshot_capacity=SLOT_CAPACITY)
    # the maintenance pass runs once, after the build (settle); none is
    # triggered while serving, so the insert burst reaches the device
    # snapshot as a delta refresh
    scfg = ServingConfig(k=K, recall_target=RECALL_TARGET, flush_size=FLUSH,
                         cache_entries=0, scan_backend="device",
                         impl="pallas", maint_min_ops=10 ** 9,
                         maint_max_ops=None)
    out = replay_runtime(wl, cfg, scfg, verbose=True, warm=True,
                         settle=True, after_replay=exhaustive)

    fp = out["snapshot"]
    cal = out["aps_calibration"]
    wit = out["after_replay"]
    share = fp["device_bytes"] / hbm_bytes
    log(f"resident N: {n_built} after the build, "
        f"{out['resident_vectors']} at the end")
    log(f"APS model as last fitted (after the burst's splits): f_M "
        f"{cal['f_m']}, "
        f"cap dimension {cal['geometry_dim']} (of {dim + 1}), "
        f"leave-one-out recall@{cal['k']} {cal['recall']:.4f} over "
        f"{cal['queries']} resident vectors; ladder {cal['tried']}")
    log(f"snapshot: {fp['partitions']} partitions x {fp['capacity']} slots "
        f"x d={fp['dim']} {fp['dtype']} = {fp['device_bytes']} bytes "
        f"({share:.1%} of {hbm_bytes / 1e9:.0f} GB HBM); live "
        f"{fp['live_vectors']} vectors = {fp['live_bytes']} bytes "
        f"({fp['live_bytes'] / fp['device_bytes']:.1%} of the snapshot); "
        f"largest partition {fp['largest_partition']}; "
        f"{out['final_partitions']} partitions at the end")
    log(f"snapshot refreshes: {fp['full_rebuilds']} full, "
        f"{fp['delta_refreshes']} delta; maintenance: one pass after the "
        f"build ({out['settle_s']}s) + {out['maintenance_runs']} while "
        f"serving")
    log(f"seconds: build {out['build_s']}, maintenance {out['settle_s']}, "
        f"compile warm-up {out['warm_s']}, staging the snapshot "
        f"{out['stage_s']}, serve {out['serve_s']}")
    log(f"compile events after warm-up: {out['compiles_in_window']}")
    log(f"statuses: {out['status_counts']}; scan_faults: "
        f"{out['scan_faults']}")
    log(f"mean recall@{K} vs exact brute force: {out['mean_recall']} "
        f"(target {RECALL_TARGET})")
    log(f"witness, the same snapshot with a fixed plan over all "
        f"{wit['nprobe']} partitions ({wit['impl']} scan): recall@{K} "
        f"{wit['recall']:.4f} over {len(queries)} queries "
        f"({wit['seconds']}s)")
    has_kernel = round_scan_has_kernel(out["last_scan"])
    log(f"compiled round scan holds tpu_custom_call: {has_kernel}")

    # the cut: what the asked size would need at the padding measured
    # here, and what sets the cut (PERF.md)
    pad = fp["device_bytes"] / fp["live_bytes"]
    asked = RESIDENT_ASKED * dim * 4 * pad
    if n_built < RESIDENT_ASKED:
        log(f"N cut: {n_built} vectors resident after the build in place "
            f"of {RESIDENT_ASKED}; at the padding measured here "
            f"({pad:.2f}x the live bytes) the asked size needs "
            f"~{asked / 1e9:.1f} GB, {asked / hbm_bytes:.0%} of HBM "
            f"(limit {HBM_SHARE_MAX:.0%}); the run's time limit sets the "
            f"cut: the maintenance pass took {out['settle_s']}s here and "
            f"grows ~N^1.6")
    n_queries = out["n_queries"]
    failed = [msg for ok, msg in [
        (n_built >= min_resident,
         f"fewer than {min_resident} vectors resident after the build"),
        (share <= HBM_SHARE_MAX,
         f"padded snapshot takes {share:.1%} of HBM (limit "
         f"{HBM_SHARE_MAX:.0%}): cut N_TOTAL"),
        (out["status_counts"].get(STATUS_OK, 0) == n_queries
         and sum(out["status_counts"].values()) == n_queries,
         f"not every query OK: {out['status_counts']}"),
        (out["scan_faults"] == 0, f"scan_faults={out['scan_faults']}"),
        (out["mean_recall"] >= RECALL_TARGET - RECALL_SLACK,
         f"mean recall {out['mean_recall']} < "
         f"{RECALL_TARGET - RECALL_SLACK}"),
        (wit["recall"] >= EXHAUSTIVE_MIN,
         f"exhaustive scan recall {wit['recall']:.4f} < {EXHAUSTIVE_MIN}"),
        (fp["delta_refreshes"] >= 1,
         "the insert burst was not applied as a delta refresh"),
        (has_kernel, "round scan compiled without tpu_custom_call"),
    ] if not ok]
    check(not failed, "; ".join(failed))


def run_sharded(n_total: int = N_TOTAL, dim: int = DIM,
                n_chips: int = 4) -> None:
    """Four chips: the partition-sharded engine over a (4, 1) mesh against
    the same engine on one chip, both against exact brute force."""
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro.core import (EngineConfig, QuakeConfig, QuakeIndex,
                            ShardedQuakeEngine)
    from repro.data.workload import IncrementalGroundTruth

    wl = make_workload(n_total, dim)
    t0 = time.time()
    index = QuakeIndex.build(wl.initial_vectors, wl.initial_ids,
                             config=QuakeConfig(
                                 metric="ip", recall_target=RECALL_TARGET,
                                 snapshot_capacity=SLOT_CAPACITY))
    log(f"built: {index.num_vectors} vectors, {index.num_partitions} "
        f"partitions ({time.time() - t0:.1f}s)")
    q = np.concatenate([op.queries for op in wl.operations
                        if op.kind == "query"])
    gt = IncrementalGroundTruth(wl.dataset, wl.initial_ids).topk(q, K)
    ecfg = EngineConfig(metric="ip", k=K, recall_target=RECALL_TARGET,
                        part_axes=("data",), batch_axis="model",
                        scan_impl="union_pallas")
    devs = np.array(jax.devices())
    recalls = {}
    for chips in (n_chips, 1):
        mesh = Mesh(devs[:chips].reshape(chips, 1), ("data", "model"))
        eng = ShardedQuakeEngine(mesh, ecfg)
        t0 = time.time()
        # one-shot APS plan: one compiled executor per mesh (the round
        # loop compiles one per union-size bucket, ~20 s each for v5e)
        res = eng.search_batch(index, q, rounds=1)
        dt = time.time() - t0
        rec = float(np.mean([len(set(r.tolist()) & set(g.tolist())) / K
                             for r, g in zip(res.ids, gt)]))
        recalls[chips] = rec
        cal = index.aps_calibration
        log(f"APS model as fitted for the {index.num_partitions} "
            f"partitions the slot capacity leaves: f_M {cal['f_m']}, cap "
            f"dimension {cal['geometry_dim']}, leave-one-out "
            f"recall@{cal['k']} {cal['recall']:.4f}")
        data = eng.refresh_snapshot(index).data
        shards = [s.data.nbytes for s in data.addressable_shards]
        log(f"{chips} chip(s): recall@{K} {rec:.4f} over {len(q)} queries "
            f"({dt:.1f}s incl. compile); snapshot {data.shape} "
            f"{data.dtype} = {data.nbytes} bytes, per device {shards}")
        if chips == n_chips:
            check(len(shards) == n_chips
                  and all(abs(b * n_chips - data.nbytes)
                          <= 0.01 * data.nbytes for b in shards),
                  f"snapshot not split evenly over {n_chips} devices: "
                  f"{shards}")
        del eng, data
    check(recalls[n_chips] >= recalls[1] - 0.02,
          f"{n_chips}-chip recall {recalls[n_chips]:.4f} < one-chip "
          f"{recalls[1]:.4f} - 0.02")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(HERE, "src"))
    try:
        from repro.launch.compile_cache import configure_compile_cache
        from repro.roofline.analysis import peaks
    except ImportError as e:
        print(f"FAIL: the repro package is not next to this script ({e})",
              file=sys.stderr)
        return 2
    cache_dir = configure_compile_cache()
    import jax
    devs = jax.devices()
    dev = devs[0]
    log(f"device: {dev.platform} / {dev.device_kind} x {len(devs)}; "
        f"compile cache: {cache_dir}")
    if dev.platform != "tpu":
        print(f"FAIL: no TPU (JAX platform is {dev.platform!r})",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"FAIL: --chips {args.chips} but JAX sees {len(devs)} "
              f"device(s)", file=sys.stderr)
        return 1
    log(f"d={DIM} is an assumption: PAPER.md does not state the width of "
        f"the Wikipedia embeddings")
    t0 = time.time()
    try:
        if args.chips == 4:
            run_sharded()
        else:
            kernel_check(DIM)
            run_served(hbm_bytes=peaks(dev.device_kind)["hbm_bytes"])
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    log(f"smoke passed in {time.time() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

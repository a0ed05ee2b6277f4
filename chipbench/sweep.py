"""Stretches of a cell's traffic after one set-up: a cell's knee, and
how far its numbers move with the seed.

    python3 chipbench/sweep.py --workload <cell> --seed <n> \\
        --seconds <s> --windows 8:1,16:1,16:2:own,16:2:own+t1

Each window is ``rate:seed[:flags]``: the cell's mix at that rate, its
gaps and queries in the order ``seed`` draws.  Flags, joined by ``+``:
``own`` draws the queries themselves from ``seed`` too (a set of its own
in place of the mix's fixed one), ``t1`` holds the host's BLAS to one
thread for the stretch.  For each window: the median and 95th percentile
from due time to result, the rate of completions, the median of the last
quarter's queries against the first quarter's (a backlog that grows
shows as a ratio well above 1), compiles, flushes and rounds.  A tool
for choosing a mix's rate and for looking at spreads; the benchmark's
runs do not use it.
"""
from __future__ import annotations

import argparse
import contextlib
import copy
import json
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--windows", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    import jax
    import numpy as np
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from threadpoolctl import threadpool_limits

    from repro import sanitize
    from chipbench import harness
    from chipbench.gen import traffic as traffic_mod

    def log(m):
        print(f"[{time.perf_counter() - T_START:7.1f}s] {m}",
              file=sys.stderr, flush=True)

    s = harness.setup(args.workload, args.seed, t_start=T_START, log=log)
    log("set up")
    for spec in args.windows.split(","):
        rate, seed, *rest = spec.split(":")
        flags = set(rest[0].split("+")) if rest else set()
        mix = copy.deepcopy(s.cell.traffic)
        mix["arrivals"]["rate_per_s"] = float(rate)
        if "own" in flags:
            mix["queries"]["seed"] = int(seed)
        sched = traffic_mod.make_schedule(
            mix, s.corpus, args.seconds,
            np.random.default_rng([int(seed), 3]), s.book,
            traffic_mod.query_rng(mix, 3))
        before = s.rt.metrics_snapshot()
        ev = sanitize.CompileEvents()
        limit = (threadpool_limits(1) if "t1" in flags
                 else contextlib.nullcontext())
        with limit:
            dr = harness.drive(s.rt, sched, s.corpus, s.state, s.period)
        compiles = ev.new()
        after = s.rt.metrics_snapshot()
        res = [s.rt.result(int(q)) for q in dr.qids]
        lat = dr.q_done - (dr.t0 + sched.q_due)
        n = len(lat)
        q4 = max(n // 4, 1)
        flushes = after.get("serving.flushes", 0) - before.get(
            "serving.flushes", 0)
        rounds = after.get("scheduler.rounds", 0) - before.get(
            "scheduler.rounds", 0)
        w_lat = dr.w_ack - (dr.t0 + sched.w_due)
        print(json.dumps({
            "window": spec, "queries": n,
            "p50_ms": float(np.percentile(lat, 50) * 1e3),
            "p95_ms": float(np.percentile(lat, 95) * 1e3),
            "completed_per_s": n / (np.nanmax(dr.q_done) - dr.t0),
            "last_over_first_quarter": float(np.median(lat[-q4:])
                                             / np.median(lat[:q4])),
            "compiles": compiles, "flushes": flushes,
            "rounds_per_flush": rounds / max(flushes, 1),
            "write_p50_ms": (float(np.percentile(w_lat, 50) * 1e3)
                             if len(w_lat) else None),
            "lag_p95_ms": float(np.percentile(
                dr.q_send - (dr.t0 + sched.q_due), 95) * 1e3),
            "nprobe_mean": float(np.mean([r.nprobe for r in res]))}),
            flush=True)
    s.rt.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())

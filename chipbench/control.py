"""The control of the comparison that decides ``correct``.

The plain reference is put in the program's place and computed at the
next lower precision than the configuration states (``HIGH``, three bf16
passes, for float32 at ``highest``): it answers every query of a window
as a run would send them, and its answers go through the same check.  The
check has to find it not correct.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 \\
        --seconds <s>

prints one JSON line per seed with each compared number and ``correct``.
The benchmark's own runs do not run it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def control(cell_name: str, seed: int, seconds: float, root=None,
            precision=None):
    """The check's reading of the reference at ``precision`` (default:
    ``HIGH``) in the program's place, on the window a run of ``seed``
    sends."""
    from . import check as check_mod
    from . import spec as spec_mod
    from .gen import corpus as corpus_mod
    from .gen import traffic as traffic_mod
    from .reference import HIGH, Reference

    precision = HIGH if precision is None else precision
    cell = spec_mod.load_cell(cell_name, root or spec_mod.ROOT)
    cfg, mix = cell.config, cell.traffic
    k = int(cfg["guarantees"]["k"])
    corpus = corpus_mod.make_corpus(cfg, seed)
    book = traffic_mod.start_runbook(corpus)
    rng_warm = np.random.default_rng([seed, 2])
    qrng_warm = traffic_mod.query_rng(mix, 2)
    for _ in range(int(mix["warmup"]["min_passes"])):
        traffic_mod.make_schedule(mix, corpus, float(mix["warmup"]["seconds"]),
                                  rng_warm, book, qrng_warm)
    sched = traffic_mod.make_schedule(mix, corpus, seconds,
                                      np.random.default_rng([seed, 3]), book,
                                      traffic_mod.query_rng(mix, 3))
    # each query sees the writes due before it, none in flight
    w = sched.w_first + np.searchsorted(sched.w_due, sched.q_due,
                                        side="right")
    ins, dele = check_mod.residency(corpus, book.history)
    ref = Reference(corpus.x, corpus.metric, ins, dele)
    d, ids = ref.topk(sched.q, w, k, precision=precision)
    served = check_mod.Served(ids=ids, dists=d.astype(np.float64),
                              ok=np.ones(len(ids), bool))
    rng_chk = np.random.default_rng([seed, 4])
    n_wit = min(int(cfg["checks"]["witness_queries"]), len(sched.q))
    rows = np.sort(rng_chk.choice(len(sched.q), n_wit, replace=False))
    _, wit = ref.topk(sched.q[rows], np.full(n_wit, len(book.history)), k,
                      precision=precision)
    return check_mod.check(cfg, corpus, sched, w, np.ones(len(w), bool),
                           served, witness_ids=wit, witness_rows=rows,
                           history=book.history, ref=ref)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from chipbench.control import control as run_control
    for seed in (int(s) for s in args.seeds.split(",")):
        chk = run_control(args.workload, seed, args.seconds)
        print(json.dumps({"seed": seed, "correct": chk.correct,
                          "checks": chk.line(), "spread": chk.spread}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The comparison that decides ``correct``.

Every query of the window is compared with the plain reference
(``reference.py``) over the rows resident when it was sent, as its
configuration's ``checks`` set out.  The numbers, each with its limit:

* ``dist_err_p99`` (scan kernels): the 99th percentile, over every
  returned id, of the gap between its returned distance and the exact
  (float64) one, over the magnitude of the terms it is computed from
  (``|q||x|`` for inner product, ``|q|^2+|x|^2`` for L2): what rounding
  leaves.  A lower precision than the configuration states shows here.
  The percentile and not the widest gap: float32 summation alone leaves
  a tail that reaches a third of what the next lower precision leaves at
  its widest, while at the 99th percentile the two stay apart.
* ``dist_err_max`` (scan kernels): the widest of the same gaps, held to
  a gross limit far above rounding, so that a fault confined to under 1%
  of the returned distances (one slot, one tile, one partition) shows.
* ``recall_mean`` (planner): mean recall@k against the exact top-k, at
  least the configuration's stated floor.
* ``bad_ids`` (snapshot): returned ids that are empty or not resident when
  the query was sent -- a deleted row, or one not yet acknowledged.
  Exact: limit 0.
* ``insert_miss`` (snapshot's delta path): of the queries drawn around a
  row of an acknowledged insert, the share that do not return that row.
* ``unanswered``: queries without an ``OK`` result.  Exact: limit 0.
* ``witness_recall`` (scan path): a fixed plan over every partition of
  the final snapshot, as the smoke's witness, against the exact top-k
  after every write: at least ``witness_recall_min``.

A query sent while a write was in flight may be answered before or after
it; those queries count for ``dist_err`` and ``unanswered`` but not for
the checks that depend on the resident rows.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional

import numpy as np

from .reference import BIG, Reference


@dataclass
class Served:
    ids: np.ndarray      # (n, k) int64, -1 where empty
    dists: np.ndarray    # (n, k) float64
    ok: np.ndarray       # (n,) bool: an OK result came back

    @classmethod
    def from_results(cls, results, k: int) -> "Served":
        n = len(results)
        ids = np.full((n, k), -1, np.int64)
        dists = np.full((n, k), np.nan)
        ok = np.zeros(n, bool)
        for i, r in enumerate(results):
            if r is None:
                continue
            ok[i] = r.status == "OK"
            ids[i] = np.asarray(r.ids)[:k]
            dists[i] = np.asarray(r.dists)[:k]
        return cls(ids, dists, ok)


@dataclass
class Check:
    numbers: Dict[str, float]
    limits: Dict[str, tuple]             # name -> (op, limit)
    recall: np.ndarray = field(default_factory=lambda: np.zeros(0))
    judged: int = 0                      # queries with a defined state
    spread: Dict[str, float] = field(default_factory=dict)  # not compared

    @property
    def passed(self) -> Dict[str, bool]:
        out = {}
        for name, (op, lim) in self.limits.items():
            v = self.numbers[name]
            out[name] = bool(v <= lim) if op == "<=" else bool(v >= lim)
        return out

    @property
    def correct(self) -> bool:
        return all(self.passed.values())

    def line(self) -> dict:
        return {name: {"value": self.numbers[name], "op": op, "limit": lim}
                for name, (op, lim) in self.limits.items()}


def residency(corpus, history) -> tuple:
    """Each row's ``ins_at`` and ``del_at`` over the runbook's writes."""
    ins = np.full(corpus.n, BIG, np.int64)
    ins[:corpus.n_resident] = -1
    dele = np.full(corpus.n, BIG, np.int64)
    for j, (kind, lo, hi) in enumerate(history):
        (ins if kind == "insert" else dele)[lo:hi] = j
    return ins, dele


def check(cfg: dict, corpus, sched, q_state: np.ndarray,
          q_clear: np.ndarray, served: Served, witness_ids: np.ndarray,
          witness_rows: np.ndarray, history,
          ref: Optional[Reference] = None) -> Check:
    k = served.ids.shape[1]
    lim = cfg["checks"]
    ins, dele = residency(corpus, history)
    if ref is None:
        ref = Reference(corpus.x, corpus.metric, ins, dele)
    q = sched.q
    numbers, limits = {}, {}

    # scan kernels: the distance of every returned id, against float64
    have = served.ids >= 0
    rd, scale = ref.exact_dist(q, corpus.x, served.ids)
    gap = (np.abs(served.dists - rd) / np.maximum(scale, 1e-30))[have]
    numbers["dist_err_p99"] = (float(np.percentile(gap, 99)) if gap.size
                               else 0.0)
    limits["dist_err_p99"] = ("<=", float(lim["dist_err_p99_max"]))
    numbers["dist_err_max"] = float(np.max(gap, initial=0.0))
    limits["dist_err_max"] = ("<=", float(lim["dist_err_max_limit"]))
    spread = {"dist_err_p50": float(np.median(gap)) if gap.size else 0.0}

    # planner and snapshot: against the rows resident when it was sent
    judged = np.nonzero(q_clear & served.ok)[0]
    _, truth = ref.topk(q[judged], q_state[judged], k)
    recall = np.asarray([len(set(s[s >= 0]) & set(t[t >= 0])) / k
                         for s, t in zip(served.ids[judged], truth)])
    numbers["recall_mean"] = float(recall.mean()) if len(recall) else 0.0
    limits["recall_mean"] = (">=", float(cfg["guarantees"]["recall_floor"]))

    sid = served.ids[judged]
    w = q_state[judged][:, None]
    safe = np.clip(sid, 0, corpus.n - 1)
    resident = (sid >= 0) & (ins[safe] < w) & (dele[safe] >= w)
    numbers["bad_ids"] = int((~resident).sum())
    limits["bad_ids"] = ("<=", 0)

    if "insert_miss_max" in lim:
        base = sched.q_base[judged]
        fresh = sched.q_fresh[judged] & (ins[base] >= 0) \
            & (ins[base] < q_state[judged]) & (dele[base] >= q_state[judged])
        hit = (sid == base[:, None]).any(axis=1)
        numbers["insert_miss"] = (float((~hit[fresh]).mean())
                                  if fresh.any() else 0.0)
        limits["insert_miss"] = ("<=", float(lim["insert_miss_max"]))

    numbers["unanswered"] = int((~served.ok).sum())
    limits["unanswered"] = ("<=", 0)

    final = np.full(len(witness_rows), len(history), np.int64)
    _, wtruth = ref.topk(q[witness_rows], final, k)
    numbers["witness_recall"] = float(np.mean(
        [len(set(s[s >= 0]) & set(t[t >= 0])) / k
         for s, t in zip(np.asarray(witness_ids), wtruth)]))
    limits["witness_recall"] = (">=", float(lim["witness_recall_min"]))
    return Check(numbers, limits, recall=recall, judged=len(judged),
                 spread=spread)

"""The one traffic generator: a mix file of parameters in, a schedule out.

A mix (``chipbench/traffic/<name>.json``) gives

* ``arrivals``: open-loop Poisson queries at ``rate_per_s``.  The gaps are
  the exponential distribution's quantiles at evenly spaced levels, in an
  order drawn from the seed: every seed offers the same gaps, so the same
  work, in another order (copied from ``benchmarks/bench_serving.py``'s
  exponential arrivals, which drew the gaps themselves);
* ``queries``: how a query picks its base vector, jittered by ``jitter``
  as ``repro.data.wikipedia`` and ``repro.data.workload.generate`` do:
  ``page_zipf`` (Zipf ``zipf_a`` over every resident page by a popularity
  rank that drifts for ``months`` months, the Wikipedia trace) or
  ``cluster_zipf`` (Zipf ``zipf_a`` over clusters, hot set permuted by the
  seed, then a resident row of the cluster); with ``fresh_share`` that
  share of queries looks for a row of the latest insert instead.  A mix
  whose ``queries`` names a ``seed`` of its own draws one fixed set of
  queries from it (the popularity, the rows and their jitter; see
  ``query_rng``): every run's seed then offers the same queries, in an
  order of its own, as it offers the same gaps;
* ``writes`` (optional): the clustered streaming runbook, one write every
  ``interval_s`` in the order of ``pattern``; an insert adds the next
  ``batch`` rows in cluster order, a delete retires the ``batch`` oldest,
  so the resident count stays level.

Warm-up and window are cut from one runbook, so the window's writes carry
on where the warm-up's stopped.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

from .corpus import Corpus, zipf_weights


@dataclass
class Schedule:
    q_due: np.ndarray          # (nq,) offsets from the window's start, s
    q: np.ndarray              # (nq, d) float32 query vectors
    q_base: np.ndarray         # (nq,) row each query was drawn around
    q_fresh: np.ndarray        # (nq,) bool: drawn from the latest insert
    w_due: np.ndarray          # (nw,) offsets of the writes
    w_kind: List[str]          # "insert" | "delete"
    w_lo: np.ndarray           # (nw,) first row of each write
    w_hi: np.ndarray           # (nw,) one past its last row
    w_first: int = 0           # runbook index of this schedule's first write


@dataclass
class Runbook:
    """Resident rows are ``[lo, hi)`` in corpus order."""
    lo: int
    hi: int
    writes: int = 0
    last_insert: Optional[tuple] = None
    history: List[tuple] = field(default_factory=list)  # (kind, lo, hi)


def poisson_offsets(rate: float, seconds: float,
                    rng: np.random.Generator) -> np.ndarray:
    n = max(int(round(rate * seconds)), 1)
    levels = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-levels) / rate
    gaps = gaps[rng.permutation(n)]
    # the first op comes one gap after the start, the last half a mean
    # gap before the end
    return np.cumsum(gaps) * (seconds * (1.0 - 0.5 / n) / gaps.sum())


def query_rng(mix: dict, stream: int) -> Optional[np.random.Generator]:
    """The generator one stretch's queries are drawn from when the mix
    names a ``seed`` of its own (stream 0 is the popularity), else None:
    the run's own generator draws them."""
    qseed = mix["queries"].get("seed")
    return None if qseed is None else np.random.default_rng(
        [int(qseed), stream])


def start_runbook(corpus: Corpus) -> Runbook:
    return Runbook(lo=0, hi=corpus.n_resident)


def make_schedule(mix: dict, corpus: Corpus, seconds: float,
                  rng: np.random.Generator, book: Runbook,
                  qrng: Optional[np.random.Generator] = None) -> Schedule:
    """One stretch of traffic (``seconds`` long) from ``book``'s state;
    advances ``book`` past its writes.  ``rng`` (the run's) draws the
    order of the gaps and of the queries; ``qrng`` (``query_rng``), where
    given, the queries themselves."""
    qmix = mix["queries"]
    pop_rng = query_rng(mix, 0) or qrng or rng
    qrng = qrng or rng
    q_due = poisson_offsets(float(mix["arrivals"]["rate_per_s"]), seconds,
                            rng)
    nq = len(q_due)

    wmix = mix.get("writes")
    w_due, w_kind, w_lo, w_hi = [], [], [], []
    first = book.writes
    # runbook state after the first j writes of this stretch: resident
    # range and the latest insert (what a fresh query looks for)
    states = [(book.lo, book.hi) + (book.last_insert or (0, 0))]
    if wmix:
        step = float(wmix["interval_s"])
        batch = int(wmix["batch"])
        pattern = wmix["pattern"]
        t = step / 2
        while t < seconds:
            kind = pattern[book.writes % len(pattern)]
            if kind == "insert":
                lo, hi = book.hi, book.hi + batch
                if hi > corpus.n:
                    raise ValueError(
                        f"the runbook ran out of rows: {corpus.n} made, "
                        f"{hi} needed; raise the config's n_total")
                book.hi = hi
                book.last_insert = (lo, hi)
            elif kind == "delete":
                lo, hi = book.lo, book.lo + batch
                book.lo = hi
            else:
                raise ValueError(f"unknown write kind {kind!r}")
            w_due.append(t)
            w_kind.append(kind)
            w_lo.append(lo)
            w_hi.append(hi)
            book.history.append((kind, lo, hi))
            book.writes += 1
            states.append((book.lo, book.hi)
                          + (book.last_insert or (0, 0)))
            t += step
    w_due = np.asarray(w_due, np.float64)

    # each query is drawn from the runbook state its due time falls in
    seg = np.searchsorted(w_due, q_due, side="right")
    st = np.asarray(states, np.int64)[seg]
    lo_q, hi_q, last_q = st[:, 0], st[:, 1], st[:, 2:]
    base = np.empty(nq, np.int64)
    fresh = np.zeros(nq, bool)
    pop = qmix["popularity"]
    if pop == "page_zipf":
        if wmix:
            raise ValueError("page_zipf draws over a fixed resident set")
        res = np.arange(lo_q[0], hi_q[0])
        rank = pop_rng.permutation(len(res)).astype(np.float64)
        for _ in range(int(qmix["months"])):
            rank += pop_rng.normal(size=len(res)) * qmix["drift"] * len(res)
        probs = np.empty(len(res))
        probs[np.argsort(rank)] = zipf_weights(len(res), qmix["zipf_a"])
        base[:] = qrng.choice(res, size=nq, p=probs)
    elif pop == "cluster_zipf":
        g = len(corpus.group_start)
        w = zipf_weights(g, qmix["zipf_a"])[pop_rng.permutation(g)]
        cl = qrng.choice(g, size=nq, p=w)
        sizes = np.bincount(corpus.group, minlength=g)
        c_lo = np.maximum(corpus.group_start[cl], lo_q)
        c_hi = np.minimum(corpus.group_start[cl] + sizes[cl], hi_q)
        empty = c_hi <= c_lo
        c_lo = np.where(empty, lo_q, c_lo)
        c_hi = np.where(empty, hi_q, c_hi)
        base[:] = c_lo + (qrng.random(nq) * (c_hi - c_lo)).astype(np.int64)
        share = float(qmix.get("fresh_share", 0.0))
        pick = (qrng.random(nq) < share) & (last_q[:, 1] > last_q[:, 0])
        span = last_q[:, 1] - last_q[:, 0]
        fresh_row = last_q[:, 0] + (qrng.random(nq) * np.maximum(span, 1)
                                    ).astype(np.int64)
        base = np.where(pick, fresh_row, base)
        fresh = pick
    else:
        raise ValueError(f"unknown query popularity {pop!r}")
    q = corpus.x[base] + qrng.normal(
        size=(nq, corpus.dim)).astype(np.float32) * qmix["jitter"]
    # the run's order of the queries, within each stretch between writes
    # (where a query's rows are the same)
    order = np.lexsort((rng.random(nq), seg))
    q, base, fresh = q[order], base[order], fresh[order]
    return Schedule(q_due=q_due,
                    q=q.astype(np.float32), q_base=base, q_fresh=fresh,
                    w_due=w_due, w_kind=w_kind,
                    w_lo=np.asarray(w_lo, np.int64),
                    w_hi=np.asarray(w_hi, np.int64), w_first=first)


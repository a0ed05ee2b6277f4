"""The plain reference of both configurations: exact k-nearest-neighbour
search by brute force in ``jax.numpy``, float32, at the highest matmul
precision, over the rows resident when each query was sent.

It imports nothing of the program and takes nothing the program made:
the corpus comes from the benchmark's own generator.  The distances of the
ids a query returned are also computed exactly, in float64 on the host.  Distances follow
the program's convention, smaller is nearer: ``-q.x`` for inner product,
``max(|x|^2 - 2 q.x + |q|^2, 0)`` for L2.  The corpus is scanned in blocks
of rows and the queries in blocks of ``Q_BLOCK``, so it fits on the chip
beside nothing else.

``precision`` is for the control only: the same search at the next lower
precision put in the program's place, ``HIGH``: three bf16 passes, written
out here (each operand split into a bf16 high part and a bf16 remainder,
the three larger cross products summed in float32), so that it computes
the same on every backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = "highest"
HIGH = "high"
ROW_BLOCK = 32768
Q_BLOCK = 128
BIG = np.iinfo(np.int32).max
MISS = 3.0e38          # distance of an empty top-k slot


def _bf16(a):
    # rounds to bf16 in float32; unlike a pair of casts, no compiler may
    # drop it as excess precision
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _split(a):
    hi = _bf16(a)
    return hi, _bf16(a - hi)


def _dot(a, b, spec, precision):
    """``einsum(spec, a, b)`` in float32 at the highest precision, or at
    ``HIGH``'s three bf16 passes."""
    full = jax.lax.Precision.HIGHEST
    if precision == HIGHEST:
        return jnp.einsum(spec, a, b, precision=full)
    (ah, al), (bh, bl) = _split(a), _split(b)
    return (jnp.einsum(spec, ah, bh, precision=full)
            + jnp.einsum(spec, ah, bl, precision=full)
            + jnp.einsum(spec, al, bh, precision=full))


def _dist(q, x, metric, precision):
    qx = _dot(q, x, "bd,nd->bn", precision)
    if metric == "ip":
        return -qx
    q2 = jnp.sum(q * q, axis=1)[:, None]
    x2 = jnp.sum(x * x, axis=1)[None, :]
    return jnp.maximum(x2 - 2.0 * qx + q2, 0.0)


@functools.partial(jax.jit, static_argnames=("k", "metric", "precision"))
def _block_topk(best_d, best_i, q, w, xb, ins_b, del_b, row0, k, metric,
                precision):
    d = _dist(q, xb, metric, precision)
    alive = (ins_b[None, :] < w[:, None]) & (del_b[None, :] >= w[:, None])
    d = jnp.where(alive, d, MISS)
    ids = row0 + jnp.arange(xb.shape[0], dtype=jnp.int32)
    cat_d = jnp.concatenate([best_d, d], axis=1)
    cat_i = jnp.concatenate(
        [best_i, jnp.broadcast_to(ids[None, :], d.shape)], axis=1)
    neg, pos = jax.lax.top_k(-cat_d, k)
    return -neg, jnp.take_along_axis(cat_i, pos, axis=1)


class Reference:
    """The corpus on the device, padded to whole blocks, with each row's
    residency: a row is resident for a query that saw ``w`` writes when
    ``ins_at < w <= del_at`` (``ins_at`` -1 for rows built in, ``del_at``
    ``BIG`` for rows never deleted)."""

    def __init__(self, x: np.ndarray, metric: str,
                 ins_at: np.ndarray = None, del_at: np.ndarray = None):
        n, d = x.shape
        n_pad = -(-n // ROW_BLOCK) * ROW_BLOCK
        xp = np.zeros((n_pad, d), np.float32)
        xp[:n] = x
        ins = np.full(n_pad, BIG, np.int64)
        ins[:n] = -1 if ins_at is None else ins_at
        dele = np.full(n_pad, BIG, np.int64)
        if del_at is not None:
            dele[:n] = del_at
        self.metric = metric
        self.n = n
        self.x = jnp.asarray(xp)
        self.ins = jnp.asarray(np.clip(ins, -1, BIG).astype(np.int32))
        self.dele = jnp.asarray(np.clip(dele, -1, BIG).astype(np.int32))

    def topk(self, q: np.ndarray, w: np.ndarray, k: int,
             precision=HIGHEST):
        """Exact top-``k`` (distances ascending, ids) of each query over
        the rows resident after ``w[i]`` writes."""
        out_d, out_i = [], []
        for s in range(0, len(q), Q_BLOCK):
            qb, wb = _pad(q[s:s + Q_BLOCK], w[s:s + Q_BLOCK])
            best_d = jnp.full((len(qb), k), MISS, jnp.float32)
            best_i = jnp.full((len(qb), k), -1, jnp.int32)
            qd, wd = jnp.asarray(qb), jnp.asarray(wb)
            for r in range(0, self.x.shape[0], ROW_BLOCK):
                best_d, best_i = _block_topk(
                    best_d, best_i, qd, wd, self.x[r:r + ROW_BLOCK],
                    self.ins[r:r + ROW_BLOCK], self.dele[r:r + ROW_BLOCK],
                    jnp.int32(r), k=k, metric=self.metric,
                    precision=precision)
            n = min(Q_BLOCK, len(q) - s)
            out_d.append(np.asarray(best_d)[:n])
            out_i.append(np.asarray(best_i)[:n])
        d = np.concatenate(out_d)
        i = np.concatenate(out_i).astype(np.int64)
        return d, np.where(d >= MISS, -1, i)

    def exact_dist(self, q: np.ndarray, x: np.ndarray, ids: np.ndarray):
        """Each query's distance to each of its ``ids``, in float64 on the
        host: the rounding of any float32 computation shows against it.
        Also the magnitude of the terms each distance is made of, the
        yardstick of that rounding: ``|q||x|`` for inner product,
        ``|q|^2 + |x|^2`` for L2.  NaN for -1."""
        q64 = q.astype(np.float64)
        x64 = x[np.clip(ids, 0, self.n - 1)].astype(np.float64)
        qx = np.einsum("bd,bkd->bk", q64, x64)
        qn = np.sqrt(np.einsum("bd,bd->b", q64, q64))[:, None]
        xn = np.sqrt(np.einsum("bkd,bkd->bk", x64, x64))
        if self.metric == "ip":
            d, scale = -qx, qn * xn
        else:
            d, scale = np.maximum(xn ** 2 - 2.0 * qx + qn ** 2, 0.0), \
                qn ** 2 + xn ** 2
        return np.where(ids >= 0, d, np.nan), scale


def _pad(a: np.ndarray, b: np.ndarray):
    n = len(a)
    if n == Q_BLOCK:
        return a, b
    pa = np.zeros((Q_BLOCK,) + a.shape[1:], a.dtype)
    pb = np.zeros((Q_BLOCK,) + b.shape[1:], b.dtype)
    pa[:n], pb[:n] = a, b
    return pa, pb

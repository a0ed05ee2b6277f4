"""Seeded corpora of the benchmark's configurations, made on the device.

Two kinds, named by ``config["corpus"]["kind"]``:

* ``wiki_topics`` -- the Wikipedia-style corpus of the Quake paper's
  section 7.1 workload, as ``repro.data.wikipedia`` makes it: topics of
  Zipf sizes around centres drawn at ``center_scale``, pages as centre plus
  unit Gaussian noise, each page scaled to the norm ``norm`` (inner
  product).  Every page is resident.
* ``clustered`` -- the power-law Gaussian mixture of
  ``repro.data.datasets.clustered`` (L2), laid out in cluster order as
  the streaming runbook consumes it: the first ``n_resident`` rows are
  resident after the build, the rest arrive cluster by cluster.

Both are copies, so that a change to the program's generators cannot move
this yardstick.  Cluster and topic sizes are the rounded expected sizes
of the source's multinomial draw, so every seed builds the same amount of
work; the seed moves the centres, the points and the order.  The vectors
are made in one jitted call on the device and handed to the host once.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np


@dataclass
class Corpus:
    x: np.ndarray            # (n, d) float32; row index is the external id
    group: np.ndarray        # (n,) generating topic / cluster of each row
    group_start: np.ndarray  # (g,) first row of each group (rows are
                             # grouped in order)
    n_resident: int          # rows [0, n_resident) are built; the rest
                             # wait for the runbook's inserts
    metric: str

    @property
    def n(self) -> int:
        return self.x.shape[0]

    @property
    def dim(self) -> int:
        return self.x.shape[1]


def seed32(seed: int, stream: int = 0) -> int:
    """A 32-bit key for ``jax.random`` from any non-negative seed (the
    benchmark's seeds do not fit 32 bits)."""
    return int(np.random.SeedSequence([seed, stream]).generate_state(1)[0])


def zipf_weights(n: int, a: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** a
    return w / w.sum()


def expected_sizes(n: int, weights: np.ndarray) -> np.ndarray:
    """Sizes summing to ``n`` in proportion to ``weights`` (largest
    remainders): the multinomial's mean, the same for every seed."""
    raw = weights * n
    sizes = np.floor(raw).astype(np.int64)
    rest = n - int(sizes.sum())
    sizes[np.argsort(raw - sizes)[::-1][:rest]] += 1
    return sizes


def _gaussian_groups(key, group, scale, n_groups, dim, center_scale,
                     norm):
    kc, kx = jax.random.split(key)
    centers = jax.random.normal(kc, (n_groups, dim), jnp.float32) \
        * center_scale
    x = centers[group] + jax.random.normal(kx, (group.shape[0], dim),
                                           jnp.float32) * scale[group][:, None]
    if norm is not None:
        x = x * (norm / jnp.maximum(
            jnp.linalg.norm(x, axis=1, keepdims=True), 1e-6))
    return x


_make = jax.jit(_gaussian_groups, static_argnames=(
    "n_groups", "dim", "center_scale", "norm"))


def make_corpus(config: dict, seed: int) -> Corpus:
    """The corpus of a run of ``seed``.  A configuration whose ``corpus``
    names a ``seed`` of its own is one fixed dataset, as a public
    benchmark's is: every run builds the same index, and the run's seed
    draws only its traffic."""
    c = config["corpus"]
    seed = int(c.get("seed", seed))
    kind = c["kind"]
    dim = int(config["dim"])
    n = int(config["n_total"])
    rng = np.random.default_rng([seed, 1])
    if kind == "wiki_topics":
        n_groups = int(c["n_topics"])
        sizes = expected_sizes(n, zipf_weights(n_groups, c["size_zipf"]))
        scale = np.ones(n_groups, np.float32)
        norm = float(c["norm"])
        n_resident = n
    elif kind == "clustered":
        n_groups = int(c["n_clusters"])
        sizes = expected_sizes(n, zipf_weights(n_groups, c["power"]))
        # each cluster's spread, as the source draws it
        scale = (c["spread"] * (0.5 + rng.random(n_groups))).astype(
            np.float32)
        norm = None
        n_resident = int(config["n_resident"])
    else:
        raise ValueError(f"unknown corpus kind {kind!r}")
    # the runbook's cluster order (inserts arrive cluster by cluster) is
    # the seed's; the wiki topics keep theirs
    order = rng.permutation(n_groups) if kind == "clustered" \
        else np.arange(n_groups)
    group = np.repeat(order, sizes[order]).astype(np.int32)
    start = np.zeros(n_groups, np.int64)
    start[order] = np.cumsum(sizes[order]) - sizes[order]
    x = _make(jax.random.key(seed32(seed)), jnp.asarray(group),
              jnp.asarray(scale), n_groups=n_groups, dim=dim,
              center_scale=float(c["center_scale"]), norm=norm)
    x = np.asarray(x)
    return Corpus(x=x, group=group, group_start=start,
                  n_resident=n_resident, metric=config["metric"])

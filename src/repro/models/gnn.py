"""GAT (Velickovic et al., arXiv:1710.10903) via edge-list message passing.

JAX sparse is BCOO-only, so message passing is built from first principles:
gather endpoints -> per-edge attention scores -> ``segment_softmax`` over
destination -> ``segment_sum`` scatter (kernel taxonomy §GNN: SDDMM ->
edge-softmax -> SpMM, expressed as segment ops).

Distribution: **edge-parallel** — the edge list is sharded across the data
axes; every segment reduction takes a local partial then a ``psum`` over the
axis (pass ``axis=("pod","data")`` inside ``jax.shard_map``).  Node
features are
replicated (fine for Cora/molecule; ogb_products keeps features resident and
trades the replicated gather — see DESIGN.md §6 / the §Perf log).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import dense_init

Array = jax.Array


@dataclass(frozen=True)
class GATConfig:
    d_in: int
    d_hidden: int = 8
    n_heads: int = 8
    n_layers: int = 2
    n_classes: int = 7
    negative_slope: float = 0.2
    dp_axes: Tuple[str, ...] = ("pod", "data")


def init_params(key: Array, cfg: GATConfig) -> Dict[str, Any]:
    layers = []
    d_in = cfg.d_in
    for i in range(cfg.n_layers):
        last = i == cfg.n_layers - 1
        heads = 1 if last else cfg.n_heads
        d_out = cfg.n_classes if last else cfg.d_hidden
        k1, k2, k3, key = jax.random.split(key, 4)
        layers.append({
            "w": dense_init(k1, (d_in, heads, d_out), 0),
            "a_src": dense_init(k2, (heads, d_out), 1),
            "a_dst": dense_init(k3, (heads, d_out), 1),
            "b": jnp.zeros((heads, d_out)),
        })
        d_in = d_out * heads
    return {"layers": layers}


def param_specs(cfg: GATConfig) -> Dict[str, Any]:
    return {"layers": [{"w": P(None, None, None), "a_src": P(None, None),
                        "a_dst": P(None, None), "b": P(None, None)}
                       for _ in range(cfg.n_layers)]}


def _psum(x: Array, axis) -> Array:
    return jax.lax.psum(x, axis) if axis is not None else x


def _pmax(x: Array, axis) -> Array:
    return jax.lax.pmax(x, axis) if axis is not None else x


def gat_layer(lp: Dict[str, Array], h: Array, src: Array, dst: Array,
              n_nodes: int, cfg: GATConfig, last: bool,
              axis=None) -> Array:
    """One GAT layer over (possibly sharded) edges.

    h: (N, d_in) node features (replicated); src/dst: (E_loc,) local edges.
    """
    wh = jnp.einsum("nd,dho->nho", h, lp["w"].astype(h.dtype))  # (N,H,dO)
    s_src = jnp.sum(wh * lp["a_src"].astype(h.dtype), axis=-1)  # (N,H)
    s_dst = jnp.sum(wh * lp["a_dst"].astype(h.dtype), axis=-1)
    e = s_src[src] + s_dst[dst]                                 # (E,H)
    e = jax.nn.leaky_relu(e, cfg.negative_slope)

    # distributed segment softmax over incoming edges of each dst.
    # stop_gradient: max-subtraction is gradient-neutral in softmax and
    # pmax has no differentiation rule.
    smax = jax.ops.segment_max(jax.lax.stop_gradient(e), dst,
                               num_segments=n_nodes)
    smax = _pmax(jnp.nan_to_num(smax, neginf=-1e30), axis)
    smax = jnp.maximum(smax, -1e30)
    ex = jnp.exp(e - smax[dst])
    denom = _psum(jax.ops.segment_sum(ex, dst, num_segments=n_nodes), axis)
    alpha = ex / jnp.maximum(denom[dst], 1e-20)                 # (E,H)

    msg = wh[src] * alpha[..., None]                            # (E,H,dO)
    out = _psum(jax.ops.segment_sum(msg, dst, num_segments=n_nodes), axis)
    out = out + lp["b"].astype(h.dtype)
    if last:
        return jnp.mean(out, axis=1)                            # avg heads
    return jax.nn.elu(out.reshape(n_nodes, -1))                 # concat


def forward(params: Dict[str, Any], feats: Array, src: Array, dst: Array,
            cfg: GATConfig, axis=None) -> Array:
    """Node logits (N, n_classes)."""
    h = feats
    n_nodes = feats.shape[0]
    for i, lp in enumerate(params["layers"]):
        h = gat_layer(lp, h, src, dst, n_nodes, cfg,
                      last=(i == cfg.n_layers - 1), axis=axis)
    return h


def loss_fn(params: Dict[str, Any], feats: Array, src: Array, dst: Array,
            labels: Array, cfg: GATConfig, axis=None,
            label_mask: Optional[Array] = None) -> Array:
    logits = forward(params, feats, src, dst, cfg, axis=axis)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None].astype(jnp.int32),
                               axis=-1)[:, 0]
    nll = lse - gold
    if label_mask is not None:
        return jnp.sum(nll * label_mask) / jnp.maximum(
            jnp.sum(label_mask), 1.0)
    return jnp.mean(nll)


def graph_pool_logits(params: Dict[str, Any], feats: Array, src: Array,
                      dst: Array, graph_of: Array, n_graphs: int,
                      cfg: GATConfig, axis=None) -> Array:
    """Batched-small-graph mode (``molecule`` shape): mean-pool node
    representations per graph -> graph logits."""
    node_logits = forward(params, feats, src, dst, cfg, axis=axis)
    sums = jax.ops.segment_sum(node_logits, graph_of, num_segments=n_graphs)
    cnt = jax.ops.segment_sum(jnp.ones_like(graph_of, jnp.float32),
                              graph_of, num_segments=n_graphs)
    return sums / jnp.maximum(cnt[:, None], 1.0)

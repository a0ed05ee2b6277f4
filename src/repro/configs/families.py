"""Per-family Lowering builders (LM / GNN / RecSys).

Shapes are the assignment's cells; ``smoke=True`` swaps in tiny dimensions
(same code path, CPU-runnable).  All full-size arguments are
ShapeDtypeStructs — nothing allocates.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map
from ..models import gnn, recsys, transformer as tr
from ..train import optimizer as opt, steps
from .base import SDS, Lowering, dp_axes_for, named_sharding_tree

OPT_CFG = opt.AdamWConfig()


def _adapt_lm_cfg(cfg: tr.TransformerConfig, mesh: Mesh
                  ) -> tr.TransformerConfig:
    # grouped-GQA attention when the 5-D (b,s,g,rep,d) query reshape keeps
    # a tp-divisible head factor; otherwise the repeat path shards cleanly
    tp = int(mesh.shape.get(cfg.tp_axis, 1))
    rep = cfg.n_heads // cfg.n_kv_heads
    grouped = (cfg.n_kv_heads % tp == 0) or (rep % tp == 0)
    return dataclasses.replace(cfg, dp_axes=dp_axes_for(mesh),
                               attn_grouped=grouped)


def _param_shardings(mesh, spec_tree):
    return named_sharding_tree(mesh, spec_tree)


def _opt_shardings(mesh, param_sh):
    return opt.AdamWState(step=NamedSharding(mesh, P()),
                          m=param_sh, v=param_sh)


# ---------------------------------------------------------------------------
# LM family
# ---------------------------------------------------------------------------

LM_SHAPES = {
    "train_4k": dict(kind="train", seq=4096, batch=256),
    "prefill_32k": dict(kind="prefill", seq=32768, batch=32),
    "decode_32k": dict(kind="decode", seq=32768, batch=128),
    "long_500k": dict(kind="decode", seq=524288, batch=1, seq_shard=True),
}
LM_SMOKE_SHAPES = {
    "train_4k": dict(kind="train", seq=64, batch=2),
    "prefill_32k": dict(kind="prefill", seq=128, batch=2),
    "decode_32k": dict(kind="decode", seq=128, batch=2),
    "long_500k": dict(kind="decode", seq=256, batch=1, seq_shard=True),
}


def build_lm(cfg: tr.TransformerConfig, shape: str, mesh: Mesh,
             smoke: bool = False, loss_chunk: int = 512,
             microbatches: int = 2, cast_params: bool = True) -> Lowering:
    sh = dict((LM_SMOKE_SHAPES if smoke else LM_SHAPES)[shape])
    cfg = _adapt_lm_cfg(cfg, mesh)
    if smoke and sh["batch"] > 1:
        # smoke batches must divide the dp shard count of whatever mesh
        import numpy as _np
        n_dp = int(_np.prod([mesh.shape[a] for a in cfg.dp_axes]))
        sh["batch"] = max(sh["batch"], n_dp)
    dp = cfg.dp_axes
    key = jax.random.PRNGKey(0)
    params_s = jax.eval_shape(lambda k: tr.init_params(k, cfg), key)
    pspec = tr.param_specs(cfg)
    psh = _param_shardings(mesh, pspec)
    rep = NamedSharding(mesh, P())

    if sh["kind"] == "train":
        opt_s = jax.eval_shape(opt.init_state, params_s)
        osh = _opt_shardings(mesh, psh)
        batch = {"tokens": SDS((sh["batch"], sh["seq"]), jnp.int32)}
        bsh = {"tokens": NamedSharding(mesh, P(dp, None))}
        loss = functools.partial(_lm_loss_adapter, cfg=cfg,
                                 chunk=loss_chunk)
        mb = 1 if smoke else microbatches
        # cast params to compute dtype once per step so FSDP all-gathers
        # move bf16, not f32 master weights (§Perf hillclimb 2, iter 1)
        cast = cfg.compute_dtype if (
            cast_params and cfg.compute_dtype != cfg.param_dtype) else None
        fn = steps.make_train_step(loss, OPT_CFG, microbatches=mb,
                                   cast_dtype=cast)
        return Lowering(
            mesh=mesh, fn=fn, args=(params_s, opt_s, batch),
            in_shardings=(psh, osh, bsh),
            donate_argnums=(0, 1),
            description=f"lm train B={sh['batch']} S={sh['seq']} mb={mb}")

    if sh["kind"] == "prefill":
        tokens = SDS((sh["batch"], sh["seq"]), jnp.int32)
        tsh = NamedSharding(mesh, P(dp, None))
        fn = functools.partial(_lm_prefill_adapter, cfg=cfg)
        return Lowering(
        mesh=mesh, fn=fn, args=(params_s, tokens),
                        in_shardings=(psh, tsh),
                        description=f"lm prefill B={sh['batch']} "
                                    f"S={sh['seq']}")

    # decode (incl. long_500k: sequence-sharded KV cache, flash-decoding)
    b, s = sh["batch"], sh["seq"]
    l, k, dh = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    cache = SDS((l, b, s, k, dh), jnp.bfloat16)
    # head-shard the cache only when kv heads divide the tp axis (MQA/GQA
    # usually don't at tp=16); otherwise shard the sequence dim — XLA then
    # emits the flash-decoding partial-softmax collectives.
    tp_size = mesh.shape[cfg.tp_axis]
    seq_shard = sh.get("seq_shard", False) or (cfg.n_kv_heads % tp_size != 0)
    cspec = tr.cache_specs(cfg, seq_shard=seq_shard)
    if seq_shard and b == 1:
        # batch cannot shard: spread the sequence over every axis
        cspec = P(None, None, dp + (cfg.tp_axis,), None, None)
    csh = NamedSharding(mesh, cspec)
    token = SDS((b,), jnp.int32)
    clen = SDS((b,), jnp.int32)
    tsh = NamedSharding(mesh, P(dp) if b > 1 else P())
    fn = functools.partial(_lm_decode_adapter, cfg=cfg)
    return Lowering(
        mesh=mesh, fn=fn,
                    args=(params_s, token, cache, cache, clen),
                    in_shardings=(psh, tsh, csh, csh, tsh),
                    donate_argnums=(2, 3),
                    description=f"lm decode B={b} ctx={s}"
                                f"{' seq-sharded' if seq_shard else ''}")


def _lm_loss_adapter(params, batch, *, cfg, chunk):
    return tr.lm_loss_chunked(params, batch["tokens"], cfg, chunk=chunk)


def _lm_prefill_adapter(params, tokens, *, cfg):
    return tr.prefill(params, tokens, cfg)


def _lm_decode_adapter(params, token, ck, cv, clen, *, cfg):
    return tr.decode_step(params, token, ck, cv, clen, cfg)


# ---------------------------------------------------------------------------
# GNN family (gat-cora)
# ---------------------------------------------------------------------------

GNN_SHAPES = {
    "full_graph_sm": dict(kind="full", n_nodes=2708, n_edges=10556,
                          d_feat=1433),
    "minibatch_lg": dict(kind="full", n_nodes=147_456, n_edges=196_608,
                         d_feat=602),   # padded 1024-seed fanout-15/10 block
    "ogb_products": dict(kind="full", n_nodes=2_449_029,
                         n_edges=61_859_140, d_feat=100),
    "molecule": dict(kind="pooled", n_graphs=128, n_nodes=30, n_edges=64,
                     d_feat=1433),
}
GNN_SMOKE_SHAPES = {
    "full_graph_sm": dict(kind="full", n_nodes=256, n_edges=1024,
                          d_feat=64),
    "minibatch_lg": dict(kind="full", n_nodes=512, n_edges=2048, d_feat=32),
    "ogb_products": dict(kind="full", n_nodes=512, n_edges=4096, d_feat=32),
    "molecule": dict(kind="pooled", n_graphs=4, n_nodes=30, n_edges=64,
                     d_feat=16),
}


def build_gnn(cfg: gnn.GATConfig, shape: str, mesh: Mesh,
              smoke: bool = False) -> Lowering:
    sh = (GNN_SMOKE_SHAPES if smoke else GNN_SHAPES)[shape]
    dp = dp_axes_for(mesh)
    cfg = dataclasses.replace(cfg, d_in=sh["d_feat"], dp_axes=dp)
    key = jax.random.PRNGKey(0)
    params_s = jax.eval_shape(lambda k: gnn.init_params(k, cfg), key)
    psh = _param_shardings(mesh, gnn.param_specs(cfg))
    opt_s = jax.eval_shape(opt.init_state, params_s)
    osh = _opt_shardings(mesh, psh)
    rep = NamedSharding(mesh, P())
    esh = NamedSharding(mesh, P(dp))

    n_shards = int(np.prod([mesh.shape[a] for a in dp]))
    n_edges = -(-sh["n_edges"] // n_shards) * n_shards  # pad to shardable
    if sh["kind"] == "pooled":
        n_nodes = sh["n_graphs"] * sh["n_nodes"]
        n_edges_total = -(-sh["n_graphs"] * sh["n_edges"] * 2
                          // n_shards) * n_shards
        batch = {"src": SDS((n_edges_total,), jnp.int32),
                 "dst": SDS((n_edges_total,), jnp.int32),
                 "feats": SDS((n_nodes, sh["d_feat"]), jnp.float32),
                 "graph_of": SDS((n_nodes,), jnp.int32),
                 "labels": SDS((sh["n_graphs"],), jnp.int32)}
        bsh = {"src": esh, "dst": esh, "feats": rep, "graph_of": rep,
               "labels": rep}
        fn = _make_gnn_pooled_step(cfg, mesh, sh["n_graphs"])
    else:
        batch = {"src": SDS((n_edges,), jnp.int32),
                 "dst": SDS((n_edges,), jnp.int32),
                 "feats": SDS((sh["n_nodes"], sh["d_feat"]), jnp.float32),
                 "labels": SDS((sh["n_nodes"],), jnp.int32)}
        bsh = {"src": esh, "dst": esh, "feats": rep, "labels": rep}
        fn = _make_gnn_step(cfg, mesh)
    return Lowering(
        mesh=mesh, fn=fn, args=(params_s, opt_s, batch),
                    in_shardings=(psh, osh, bsh), donate_argnums=(0, 1),
                    description=f"gnn {shape}: {sh}")


def _make_gnn_step(cfg: gnn.GATConfig, mesh: Mesh):
    """Edge-parallel train step: grads computed inside shard_map (collectives
    in gnn.forward make per-shard grads globally correct via psum
    transpose), optimizer applied on replicated params."""
    dp = cfg.dp_axes

    def local_grad(params, batch):
        def loss(p):
            return gnn.loss_fn(p, batch["feats"], batch["src"],
                               batch["dst"], batch["labels"], cfg, axis=dp)
        l, g = jax.value_and_grad(loss)(params)
        return l, g

    def step(params, opt_state, batch):
        mapped = shard_map(
            local_grad, mesh=mesh,
            in_specs=(P(), {"src": P(dp), "dst": P(dp), "feats": P(),
                            "labels": P()}),
            out_specs=(P(), P()), check_vma=True)
        loss, grads = mapped(params, batch)
        params, opt_state, info = opt.apply_update(params, grads, opt_state,
                                                   OPT_CFG)
        return params, opt_state, {"loss": loss, **info}

    return step


def _make_gnn_pooled_step(cfg: gnn.GATConfig, mesh: Mesh, n_graphs: int):
    dp = cfg.dp_axes

    def local_grad(params, batch):
        def loss(p):
            logits = gnn.graph_pool_logits(
                p, batch["feats"], batch["src"], batch["dst"],
                batch["graph_of"], n_graphs, cfg, axis=dp)
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(
                logits, batch["labels"][:, None].astype(jnp.int32),
                axis=-1)[:, 0]
            return jnp.mean(lse - gold)
        return jax.value_and_grad(loss)(params)

    def step(params, opt_state, batch):
        mapped = shard_map(
            local_grad, mesh=mesh,
            in_specs=(P(), {"src": P(dp), "dst": P(dp), "feats": P(),
                            "graph_of": P(), "labels": P()}),
            out_specs=(P(), P()), check_vma=True)
        loss, grads = mapped(params, batch)
        params, opt_state, info = opt.apply_update(params, grads, opt_state,
                                                   OPT_CFG)
        return params, opt_state, {"loss": loss, **info}

    return step


# ---------------------------------------------------------------------------
# RecSys family
# ---------------------------------------------------------------------------

RECSYS_SHAPES = {
    "train_batch": dict(kind="train", batch=65536),
    "serve_p99": dict(kind="serve", batch=512),
    "serve_bulk": dict(kind="serve", batch=262144),
    "retrieval_cand": dict(kind="retrieval", batch=1, n_cand=1_000_000),
}
RECSYS_SMOKE_SHAPES = {
    "train_batch": dict(kind="train", batch=32),
    "serve_p99": dict(kind="serve", batch=8),
    "serve_bulk": dict(kind="serve", batch=64),
    "retrieval_cand": dict(kind="retrieval", batch=1, n_cand=512),
}

RECSYS_FNS = {
    "din": (recsys.din_init, recsys.din_specs, recsys.din_loss,
            recsys.din_forward),
    "sasrec": (recsys.sasrec_init, recsys.sasrec_specs, recsys.sasrec_loss,
               None),
    "two-tower-retrieval": (recsys.twotower_init, recsys.twotower_specs,
                            recsys.twotower_loss, None),
    "dlrm-rm2": (recsys.dlrm_init, recsys.dlrm_specs, recsys.dlrm_loss,
                 recsys.dlrm_forward),
}


def _recsys_batch_specs(model: str, mcfg, batch: int, mesh: Mesh,
                        hist_len: int):
    dp = dp_axes_for(mesh)
    bsh = NamedSharding(mesh, P(dp))
    b2 = NamedSharding(mesh, P(dp, None))
    rep = NamedSharding(mesh, P())
    batch_s = {"dense": SDS((batch, 13), jnp.float32),
               "sparse": SDS((batch, getattr(mcfg, "n_sparse", 26)),
                             jnp.int32),
               "history": SDS((batch, hist_len), jnp.int32),
               "history_mask": SDS((batch, hist_len), jnp.bool_),
               "target_item": SDS((batch,), jnp.int32),
               "label": SDS((batch,), jnp.float32)}
    specs = {"dense": b2, "sparse": b2, "history": b2, "history_mask": b2,
             "target_item": bsh, "label": bsh}
    return batch_s, specs


def build_recsys(model: str, mcfg, shape: str, mesh: Mesh,
                 smoke: bool = False) -> Lowering:
    sh = (RECSYS_SMOKE_SHAPES if smoke else RECSYS_SHAPES)[shape]
    init_fn, specs_fn, loss_fn, fwd_fn = RECSYS_FNS[model]
    key = jax.random.PRNGKey(0)
    params_s = jax.eval_shape(lambda k: init_fn(k, mcfg), key)
    psh = _param_shardings(mesh, specs_fn(mcfg))
    hist_len = getattr(mcfg, "seq_len", getattr(mcfg, "hist_len", 50))
    dp = dp_axes_for(mesh)
    rep = NamedSharding(mesh, P())

    if sh["kind"] == "train":
        batch_s, bsh = _recsys_batch_specs(model, mcfg, sh["batch"], mesh,
                                           hist_len)
        opt_s = jax.eval_shape(opt.init_state, params_s)
        osh = _opt_shardings(mesh, psh)
        fn = steps.make_train_step(
            functools.partial(_recsys_loss_adapter, loss_fn=loss_fn,
                              mcfg=mcfg), OPT_CFG)
        return Lowering(
        mesh=mesh, fn=fn, args=(params_s, opt_s, batch_s),
                        in_shardings=(psh, osh, bsh), donate_argnums=(0, 1),
                        description=f"{model} train B={sh['batch']}")

    if sh["kind"] == "serve":
        batch_s, bsh = _recsys_batch_specs(model, mcfg, sh["batch"], mesh,
                                           hist_len)
        fwd = fwd_fn or functools.partial(_recsys_score_adapter, model=model)
        fn = functools.partial(_recsys_serve_adapter, fwd=fwd, mcfg=mcfg)
        return Lowering(
        mesh=mesh, fn=fn, args=(params_s, batch_s),
                        in_shardings=(psh, bsh),
                        description=f"{model} serve B={sh['batch']}")

    # retrieval_cand: one user context against n_cand candidates
    n_shards = int(np.prod([mesh.shape[a] for a in dp + ("model",)]))
    n_cand = -(-sh["n_cand"] // n_shards) * n_shards  # pad to shardable
    user = {"history": SDS((1, hist_len), jnp.int32),
            "history_mask": SDS((1, hist_len), jnp.bool_),
            "dense": SDS((1, 13), jnp.float32)}
    ush = {"history": rep, "history_mask": rep, "dense": rep}
    cands = SDS((n_cand,), jnp.int32)
    csh = NamedSharding(mesh, P(dp + ("model",)))
    fn = functools.partial(_recsys_retrieval_adapter, model=model, mcfg=mcfg)
    return Lowering(
        mesh=mesh, fn=fn, args=(params_s, user, cands),
                    in_shardings=(psh, ush, csh),
                    description=f"{model} retrieval n_cand={n_cand}")


def _recsys_loss_adapter(params, batch, *, loss_fn, mcfg):
    return loss_fn(params, batch, mcfg)


def _recsys_serve_adapter(params, batch, *, fwd, mcfg):
    return fwd(params, batch, mcfg)


def _recsys_score_adapter(params, batch, mcfg, *, model):
    """Serve scores for the models whose natural serve output is a
    relevance score (sasrec next-item / two-tower user-item)."""
    if model == "sasrec":
        h = recsys.sasrec_encode(params, batch["history"],
                                 batch["history_mask"], mcfg)
        tgt = jnp.take(params["item_embed"], batch["target_item"], axis=0)
        return jnp.sum(h * tgt, axis=-1)
    u = recsys.user_repr(params, batch, mcfg)
    v = recsys.item_repr(params, batch["target_item"], mcfg)
    return jnp.sum(u * v, axis=-1)


def _recsys_retrieval_adapter(params, user, cand_ids, *, model, mcfg):
    """Score 1M candidates for one user — batched dot / broadcast ranking,
    never a loop.  (The ANN-served variant goes through the Quake engine —
    see examples/retrieval_serving.py.)"""
    if model == "two-tower-retrieval":
        u = recsys.user_repr(params, user, mcfg)            # (1, d)
        v = recsys.item_repr(params, cand_ids, mcfg)        # (N, d)
        return (u @ v.T)[0]
    if model == "sasrec":
        h = recsys.sasrec_encode(params, user["history"],
                                 user["history_mask"], mcfg)
        v = jnp.take(params["item_embed"], cand_ids, axis=0)
        return (h @ v.T)[0]
    if model == "din":
        n = cand_ids.shape[0]
        batch = {"history": jnp.broadcast_to(user["history"],
                                             (n,) + user["history"].shape[1:]),
                 "history_mask": jnp.broadcast_to(
                     user["history_mask"],
                     (n,) + user["history_mask"].shape[1:]),
                 "dense": jnp.broadcast_to(user["dense"], (n, 13)),
                 "target_item": cand_ids}
        return recsys.din_forward(params, batch, mcfg)
    # dlrm: vary the first sparse field (item), fix the rest
    n = cand_ids.shape[0]
    sparse = jnp.zeros((n, mcfg.n_sparse), jnp.int32)
    sparse = sparse.at[:, 0].set(cand_ids)
    batch = {"dense": jnp.broadcast_to(user["dense"], (n, mcfg.n_dense)),
             "sparse": sparse}
    return recsys.dlrm_forward(params, batch, mcfg)

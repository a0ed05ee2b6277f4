"""Optimizer / checkpoint / fault-tolerant loop / workload+pipeline tests."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import QuakeIndex
from repro.core.multiquery import batch_search, per_query_search
from repro.data import datasets, pipelines, wikipedia, workload
from repro.roofline import hlo_cost
from repro.train import (AdamWConfig, CheckpointManager, LoopConfig,
                         init_state, train_loop)
from repro.train import optimizer as opt
from repro.train import steps


def test_adamw_converges_quadratic():
    def loss(p, _):
        return jnp.sum((p["w"] - 3.0) ** 2)
    params = {"w": jnp.zeros(4)}
    st = init_state(params)
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=1,
                      total_steps=200)
    step = jax.jit(steps.make_train_step(loss, cfg))
    for s in range(150):
        params, st, m = step(params, st, None)
    np.testing.assert_allclose(np.asarray(params["w"]), 3.0, atol=0.05)


def test_grad_clipping():
    g = {"a": jnp.full(100, 10.0)}
    clipped, norm = opt.clip_by_global_norm(g, 1.0)
    assert float(norm) == pytest.approx(100.0)
    assert float(opt.global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)


def test_schedule_shape():
    cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                      min_lr_ratio=0.1)
    s = np.asarray([float(opt.schedule(cfg, jnp.asarray(t)))
                    for t in range(101)])
    assert s[0] == 0.0 and s[10] == pytest.approx(1.0, abs=0.1)
    assert s[100] == pytest.approx(0.1, abs=0.01)
    assert (np.diff(s[:10]) > 0).all()       # warmup rises
    assert (np.diff(s[20:]) <= 1e-9).all()   # decay falls


def test_int8_compression_roundtrip():
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=(256,)) * 0.01, jnp.float32)
    q, scale = opt.compress_int8(g)
    deq = opt.decompress_int8(q, scale)
    assert float(jnp.max(jnp.abs(deq - g))) <= float(scale) * 0.51


def test_checkpoint_roundtrip_and_gc():
    state = {"w": jnp.arange(6.0), "nested": [jnp.ones((2, 3))],
             "opt": init_state({"w": jnp.arange(6.0)})}
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, keep=2, async_write=False)
        for s in (1, 2, 3):
            mgr.save(s, state, block=True)
        assert len(mgr.list()) == 2          # gc keeps last 2
        restored, man = mgr.restore(state)
        assert man["step"] == 3
        for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(state)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_shape_mismatch_rejected():
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, async_write=False)
        mgr.save(1, {"w": jnp.zeros((4,))}, block=True)
        with pytest.raises(ValueError):
            mgr.restore({"w": jnp.zeros((5,))})


def test_loop_recovers_and_replays_data():
    """After an injected failure the loop must resume from the checkpoint
    step and consume the same batches (step-indexed pipeline)."""
    seen = []

    def step_fn(state, batch):
        seen.append(int(batch))
        return state + 1, {"loss": float(state)}

    fails = {13}

    def injector(s):
        if s in fails:
            fails.discard(s)
            raise RuntimeError("boom")

    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, async_write=False)
        rep = train_loop(jnp.zeros(()), step_fn, lambda s: s, mgr,
                         LoopConfig(n_steps=20, ckpt_every=5),
                         failure_injector=injector)
    assert rep.restarts == 1
    # steps 10..12 replayed after restore from ckpt@10
    assert seen.count(10) == 2 and seen.count(11) == 2
    assert sorted(set(seen)) == list(range(20))


def test_workload_generator_determinism_and_mix():
    ds = datasets.clustered(3000, 16, seed=0)
    cfg = workload.WorkloadConfig(n_operations=30, read_fraction=0.5,
                                  delete_fraction=0.3, query_skew=1.0,
                                  vectors_per_op=100, seed=7)
    w1 = workload.generate(ds, cfg)
    w2 = workload.generate(ds, cfg)
    assert [o.kind for o in w1.operations] == [o.kind for o in w2.operations]
    kinds = [o.kind for o in w1.operations]
    assert kinds.count("query") > 0 and kinds.count("insert") > 0


def test_wikipedia_workload_grows_and_skews():
    wl = wikipedia.wikipedia_workload(n_total=5000, dim=8, months=5,
                                      queries_per_month=200)
    assert wl.dataset.metric == "ip"
    inserted = sum(len(op.ids) for op in wl.operations
                   if op.kind == "insert")
    assert len(wl.initial_ids) + inserted == 5000
    # skew: query batches should reuse popular targets
    qops = [op for op in wl.operations if op.kind == "query"]
    assert len(qops) == 5


def test_multiquery_matches_perquery():
    ds = datasets.clustered(4000, 16, seed=0)
    idx = QuakeIndex.build(ds.vectors, num_partitions=64, kmeans_iters=3)
    q = datasets.queries_near(ds, 64, seed=2)
    rb = batch_search(idx, q, 10, nprobe=8)
    rp = per_query_search(idx, q, 10, nprobe=8)
    overlap = np.mean([len(set(rb.ids[i]) & set(rp.ids[i])) / 10
                       for i in range(64)])
    assert overlap >= 0.97


def test_hlo_cost_trip_counts():
    """The roofline analyzer must multiply scan bodies by trip count and
    agree with XLA on loop-free programs."""
    def scanned(x, w):
        def step(c, _):
            return c @ w, None
        return jax.lax.scan(step, x, None, length=7)[0]

    def flat(x, w):
        for _ in range(7):
            x = x @ w
        return x

    a = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    cs = jax.jit(scanned).lower(a, a).compile()
    cf = jax.jit(flat).lower(a, a).compile()
    mine_s = hlo_cost.analyze(cs.as_text())
    mine_f = hlo_cost.analyze(cf.as_text())
    xla_f = cf.cost_analysis()["flops"]
    assert mine_f.flops == pytest.approx(xla_f, rel=0.01)
    assert mine_s.flops == pytest.approx(mine_f.flops, rel=0.02)


def test_pipelines_are_step_indexed():
    tp = pipelines.TokenPipeline(100, 2, 8, seed=3)
    assert (tp.batch_at(5)["tokens"] == tp.batch_at(5)["tokens"]).all()
    assert (tp.batch_at(5)["tokens"] != tp.batch_at(6)["tokens"]).any()
    rp = pipelines.RecsysPipeline(batch=4, vocab=100)
    b5, b5b = rp.batch_at(5), rp.batch_at(5)
    for k in b5:
        np.testing.assert_array_equal(b5[k], b5b[k])

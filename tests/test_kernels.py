"""Pallas kernels vs. pure-jnp oracles: shape/dtype sweeps.

Top-k is a discrete boundary (taxonomy Part E): ties make elementwise index
comparison ill-posed, so indices are checked by set overlap (recall@k) and
distances by sorted allclose.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.scan_topk import bitonic_sort, merge_sorted_topk


def _recall(a: np.ndarray, b: np.ndarray) -> float:
    hits = [len(set(x[x >= 0].tolist()) & set(y[y >= 0].tolist()))
            / max((y >= 0).sum(), 1) for x, y in zip(a, b)]
    return float(np.mean(hits))


@pytest.mark.parametrize("metric", ["l2", "ip"])
@pytest.mark.parametrize("q,n,d,k", [
    (1, 100, 8, 5),        # tiny, unaligned
    (3, 1000, 48, 10),     # typical partition
    (5, 333, 17, 7),       # awkward shapes
    (8, 2048, 64, 100),    # paper's k=100
    (2, 57, 32, 64),       # k > n
])
def test_scan_topk_vs_oracle(metric, q, n, d, k):
    rng = np.random.default_rng(q * 1000 + n + d)
    qs = jnp.asarray(rng.normal(size=(q, d)), jnp.float32)
    xs = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    dr, ir = ref.scan_topk_ref(qs, xs, min(k, n), metric)
    dp, ip_ = ops.scan_topk(qs, xs, k, metric=metric, impl="pallas")
    kk = min(k, n)
    assert _recall(np.asarray(ip_[:, :kk]), np.asarray(ir)) >= 0.999
    np.testing.assert_allclose(np.sort(np.asarray(dp[:, :kk]), 1),
                               np.sort(np.asarray(dr), 1),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_scan_topk_dtypes(dtype):
    rng = np.random.default_rng(0)
    qs = jnp.asarray(rng.normal(size=(4, 32)), dtype)
    xs = jnp.asarray(rng.normal(size=(512, 32)), dtype)
    dp, ip_ = ops.scan_topk(qs, xs, 10, metric="l2", impl="pallas")
    dr, ir = ref.scan_topk_ref(qs.astype(jnp.float32),
                               xs.astype(jnp.float32), 10, "l2")
    # bf16 rounding shifts near-ties: require high-but-not-perfect overlap
    thresh = 0.999 if dtype == jnp.float32 else 0.8
    assert _recall(np.asarray(ip_), np.asarray(ir)) >= thresh


def test_scan_topk_masked():
    rng = np.random.default_rng(1)
    qs = jnp.asarray(rng.normal(size=(2, 16)), jnp.float32)
    xs = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    valid = jnp.asarray(np.arange(64) % 3 != 0)
    dp, ip_ = ops.scan_topk(qs, xs, 8, valid=valid, impl="pallas")
    assert not np.isin(np.asarray(ip_), np.where(~np.asarray(valid))[0]).any()


@pytest.mark.parametrize("n,c,d", [(100, 7, 8), (513, 37, 24),
                                   (1024, 128, 64), (65, 200, 16)])
def test_kmeans_assign_vs_oracle(n, c, d):
    rng = np.random.default_rng(n + c)
    xs = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    cs = jnp.asarray(rng.normal(size=(c, d)), jnp.float32)
    a_r, d_r = ref.kmeans_assign_ref(xs, cs)
    a_p, d_p = ops.kmeans_assign(xs, cs, impl="pallas")
    # ties can differ; distances must match
    np.testing.assert_allclose(np.asarray(d_p), np.asarray(d_r),
                               rtol=1e-4, atol=1e-3)
    assert np.mean(np.asarray(a_p) == np.asarray(a_r)) > 0.99


def test_bitonic_sort_sorts():
    rng = np.random.default_rng(2)
    d = jnp.asarray(rng.normal(size=(4, 128)), jnp.float32)
    i = jnp.broadcast_to(jnp.arange(128, dtype=jnp.int32), (4, 128))
    ds, is_ = jax.jit(bitonic_sort)(d, i)
    np.testing.assert_allclose(np.asarray(ds), np.sort(np.asarray(d), 1),
                               rtol=1e-6)
    # payload permuted consistently
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(d), np.asarray(is_), 1),
        np.asarray(ds), rtol=1e-6)


def test_merge_sorted_topk():
    """``run`` ascending, ``new`` descending (the kernel's merge form)."""
    rng = np.random.default_rng(3)
    a = np.sort(rng.normal(size=(2, 16)), 1).astype(np.float32)
    b = np.sort(rng.normal(size=(2, 16)), 1).astype(np.float32)[:, ::-1]
    ia = np.arange(16, dtype=np.int32)[None].repeat(2, 0)
    ib = (np.arange(16, dtype=np.int32) + 100)[None].repeat(2, 0)
    md, mi = jax.jit(merge_sorted_topk)(jnp.asarray(a), jnp.asarray(ia),
                                        jnp.asarray(b), jnp.asarray(ib))
    expect = np.sort(np.concatenate([a, b], 1), 1)[:, :16]
    np.testing.assert_allclose(np.asarray(md), expect, rtol=1e-6)
    # payload travels with its key
    lookup = [dict(zip(np.r_[ia[r], ib[r]].tolist(), np.r_[a[r], b[r]]))
              for r in range(2)]
    for r in range(2):
        np.testing.assert_array_equal(
            [lookup[r][x] for x in np.asarray(mi)[r].tolist()],
            np.asarray(md)[r])


def _lex_topk(d: np.ndarray, i: np.ndarray, k: int):
    """Reference: the k smallest (distance, index) keys per row, ascending."""
    order = np.lexsort((i, d), axis=1)[:, :k]
    return (np.take_along_axis(d, order, 1),
            np.take_along_axis(i, order, 1))


@pytest.mark.parametrize("width", [8, 128, 512])
@pytest.mark.parametrize("descending", [False, True])
@pytest.mark.parametrize("ties", [False, True])
def test_bitonic_sort_lexicographic(width, descending, ties):
    """The network sorts (distance, index) keys, so ties resolve to the
    smaller index in both directions."""
    rng = np.random.default_rng(width + 2 * descending + ties)
    d = rng.normal(size=(8, width)).astype(np.float32)
    if ties:
        d = np.round(d * 2) / 2           # few distinct values
    i = rng.permutation(8 * width).reshape(8, width).astype(np.int32)
    ds, is_ = jax.jit(bitonic_sort, static_argnums=2)(
        jnp.asarray(d), jnp.asarray(i), descending)
    ed, ei = _lex_topk(d, i, width)
    if descending:
        ed, ei = ed[:, ::-1], ei[:, ::-1]
    np.testing.assert_array_equal(np.asarray(ds), ed)
    np.testing.assert_array_equal(np.asarray(is_), ei)


@pytest.mark.parametrize("kw,ties", [(8, False), (16, True), (128, False),
                                     (128, True)])
def test_merge_sorted_topk_descending_new(kw, ties):
    """Merge of an ascending running list with a descending tile tail keeps
    the kw smallest keys of both, in ascending order, with index ties
    broken low-first."""
    rng = np.random.default_rng(kw + ties)
    scale = 2 if ties else 1000
    a = np.round(rng.normal(size=(4, kw)) * scale).astype(np.float32)
    b = np.round(rng.normal(size=(4, kw)) * scale).astype(np.float32)
    ia = rng.permutation(4 * kw).reshape(4, kw).astype(np.int32)
    ib = (rng.permutation(4 * kw) + 4 * kw).reshape(4, kw).astype(np.int32)
    a, ia = _lex_topk(a, ia, kw)
    b, ib = _lex_topk(b, ib, kw)
    md, mi = jax.jit(merge_sorted_topk)(
        jnp.asarray(a), jnp.asarray(ia),
        jnp.asarray(b[:, ::-1]), jnp.asarray(ib[:, ::-1]))
    ed, ei = _lex_topk(np.concatenate([a, b], 1),
                       np.concatenate([ia, ib], 1), kw)
    np.testing.assert_array_equal(np.asarray(md), ed)
    np.testing.assert_array_equal(np.asarray(mi), ei)


# ---------------------------------------------------------------------------
# Indexed selected-block scan (scan_topk_indexed)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("metric", ["l2", "ip"])
@pytest.mark.parametrize("p,s,d,b,u,k", [
    (12, 64, 32, 16, 5, 8),      # typical
    (8, 16, 8, 4, 8, 4),         # union = all partitions
    (32, 128, 48, 8, 3, 100),    # k > u*s? no: k clipped inside
])
def test_scan_selected_vs_oracle(metric, p, s, d, b, u, k):
    rng = np.random.default_rng(p + s + b)
    data = jnp.asarray(rng.normal(size=(p, s, d)), jnp.float32)
    valid = jnp.asarray(rng.random((p, s)) < 0.9)
    sel = jnp.asarray(rng.choice(p, u, replace=False).astype(np.int32))
    qmask = jnp.asarray(rng.random((b, u)) < 0.7)
    qs = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    d_ref, i_ref = ref.scan_selected_ref(qs, data, valid, sel, qmask,
                                         min(k, u * s), metric)
    d_pal, i_pal = ops.scan_selected_topk(qs, data, valid, sel, qmask, k,
                                          metric=metric, impl="pallas")
    kk = min(k, u * s)
    assert _recall(np.asarray(i_pal[:, :kk]), np.asarray(i_ref)) >= 0.999
    fin = np.asarray(d_ref) < 1e37
    np.testing.assert_allclose(np.asarray(d_pal[:, :kk])[fin],
                               np.asarray(d_ref)[fin], rtol=1e-4, atol=1e-3)


def test_scan_selected_bf16_storage():
    rng = np.random.default_rng(7)
    data32 = rng.normal(size=(8, 64, 16)).astype(np.float32)
    data = jnp.asarray(data32, jnp.bfloat16)
    valid = jnp.ones((8, 64), bool)
    sel = jnp.arange(8, dtype=jnp.int32)
    qs = jnp.asarray(rng.normal(size=(4, 16)), jnp.float32)
    qmask = jnp.ones((4, 8), bool)
    d_ref, i_ref = ref.scan_selected_ref(
        qs, jnp.asarray(data32), valid, sel, qmask, 10, "l2")
    d_pal, i_pal = ops.scan_selected_topk(qs, data, valid, sel, qmask, 10,
                                          metric="l2", impl="pallas")
    assert _recall(np.asarray(i_pal), np.asarray(i_ref)) >= 0.8


# ---------------------------------------------------------------------------
# Fused flash-attention forward kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,h,kh,sq,sk,d,causal", [
    (2, 8, 1, 96, 96, 32, True),     # MQA causal
    (1, 8, 2, 128, 128, 64, True),   # GQA
    (2, 4, 4, 100, 120, 32, False),  # MHA cross, unaligned lengths
    (1, 6, 2, 64, 256, 16, True),    # long kv
])
def test_flash_attention_kernel_vs_oracle(b, h, kh, sq, sk, d, causal):
    from repro.kernels.flash_attention import flash_attention_pallas
    from repro.models.layers import flash_attention as flash_ref
    rng = np.random.default_rng(b * 100 + h + sq)
    q = jnp.asarray(rng.normal(size=(b, sq, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, sk, kh, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, sk, kh, d)), jnp.float32)
    ref_o = flash_ref(q, k, v, causal=causal, q_block=32, k_block=32,
                      grouped=True)
    out = flash_attention_pallas(q, k, v, causal=causal, q_block=32,
                                 k_block=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_o),
                               rtol=2e-5, atol=2e-5)


def test_grouped_flash_matches_repeat():
    from repro.models.layers import flash_attention
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.normal(size=(2, 64, 12, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 64, 4, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 64, 4, 16)), jnp.float32)
    a = flash_attention(q, k, v, causal=True, q_block=32, k_block=32,
                        grouped=False)
    b_ = flash_attention(q, k, v, causal=True, q_block=32, k_block=32,
                         grouped=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                               rtol=1e-5, atol=1e-5)


def test_prefill_pallas_attention_matches_jnp():
    import dataclasses
    from repro.models import transformer as tr
    cfg = tr.TransformerConfig(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_head=16,
        d_ff=128, vocab_size=256, remat=False,
        compute_dtype=jnp.float32, q_block=32, k_block=32)
    params = tr.init_params(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 64)),
                       jnp.int32)
    lg_ref, _ = tr.prefill(params, toks, cfg)
    lg_pal, _ = tr.prefill(params, toks,
                           dataclasses.replace(cfg, attn_impl="pallas"))
    np.testing.assert_allclose(np.asarray(lg_pal), np.asarray(lg_ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_scan_selected_q8_residual(metric):
    """IVF residual SQ8: near-f32 ranking because the query-centroid term
    is exact; only the (small) residual carries quantization error."""
    rng = np.random.default_rng(5)
    P, S, d, B, U, k = 16, 64, 24, 8, 10, 10
    cents = rng.normal(size=(P, d)).astype(np.float32) * 4.0
    data = cents[:, None, :] + rng.normal(
        size=(P, S, d)).astype(np.float32)          # tight clusters
    from repro.kernels.scan_topk_indexed import quantize_int8_residual
    codes, scales = quantize_int8_residual(jnp.asarray(data),
                                           jnp.asarray(cents))
    valid = jnp.ones((P, S), bool)
    sel = jnp.asarray(rng.choice(P, U, replace=False).astype(np.int32))
    qmask = jnp.ones((B, U), bool)
    qs = jnp.asarray(cents[np.asarray(sel)[:B] % P]
                     + rng.normal(size=(B, d)).astype(np.float32))
    d_ref, i_ref = ref.scan_selected_ref(qs, jnp.asarray(data), valid,
                                         sel, qmask, k, metric)
    d_q8, i_q8 = ops.scan_selected_topk_q8(
        qs, codes, scales, valid, sel, qmask, k, metric=metric,
        centroids=jnp.asarray(cents))
    assert _recall(np.asarray(i_q8), np.asarray(i_ref)) >= 0.9
    fin = np.asarray(d_ref) < 1e37
    np.testing.assert_allclose(np.asarray(d_q8)[fin],
                               np.asarray(d_ref)[fin], rtol=0.05, atol=0.5)


def test_engine_int8_recall():
    from jax.sharding import Mesh
    from repro.core import (EngineConfig, IndexSnapshot, QuakeIndex,
                            ShardedQuakeEngine)
    from repro.data import datasets
    ds = datasets.clustered(3000, 16, n_clusters=16, seed=0)
    idx = QuakeIndex.build(ds.vectors, num_partitions=24, kmeans_iters=4)
    snap0 = IndexSnapshot.from_index(idx)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1),
                ("pod", "data", "model"))
    q = jnp.asarray(datasets.queries_near(ds, 24, seed=2))
    gt = ds.ground_truth(np.asarray(q), 10)
    eng = ShardedQuakeEngine(mesh, EngineConfig(
        k=10, nprobe=8, part_axes=("pod", "data"),
        scan_impl="union_pallas", storage_dtype="int8"))
    ss = eng.shard_snapshot(snap0)
    d_f, i_f = eng.search_fixed(q, ss)
    rec = np.mean([len(set(np.asarray(i_f[r]).tolist())
                       & set(gt[r].tolist())) / 10 for r in range(24)])
    assert rec >= 0.9, rec

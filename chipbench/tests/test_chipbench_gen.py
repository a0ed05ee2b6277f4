"""The benchmark's seeded generators: the same seed gives the same
operations, every seed the same amount of work."""
import json

import numpy as np
import pytest

from chipbench.gen import corpus as corpus_mod
from chipbench.gen import traffic as traffic_mod
from chipbench.tests.tiny import DATA

BIG_SEED = 2 ** 31 + 12345


def _cfg(name):
    return json.loads((DATA / f"{name}.json").read_text())


def _schedules(seed, mix_name="tiny-churn", cfg_name="tiny-clustered",
               stretches=(1.0, 2.0)):
    cfg, mix = _cfg(cfg_name), _cfg(mix_name)
    corpus = corpus_mod.make_corpus(cfg, seed)
    book = traffic_mod.start_runbook(corpus)
    rng = np.random.default_rng([seed, 2])
    return corpus, book, [traffic_mod.make_schedule(mix, corpus, s, rng,
                                                    book)
                          for s in stretches]


@pytest.mark.parametrize("cfg_name,mix_name", [
    ("tiny-wiki", "tiny-read"), ("tiny-clustered", "tiny-churn")])
def test_same_seed_same_operations(cfg_name, mix_name):
    a = _schedules(BIG_SEED, mix_name, cfg_name)
    b = _schedules(BIG_SEED, mix_name, cfg_name)
    c = _schedules(7, mix_name, cfg_name)
    np.testing.assert_array_equal(a[0].x, b[0].x)
    for sa, sb in zip(a[2], b[2]):
        np.testing.assert_array_equal(sa.q, sb.q)
        np.testing.assert_array_equal(sa.q_due, sb.q_due)
        np.testing.assert_array_equal(sa.w_lo, sb.w_lo)
        assert sa.w_kind == sb.w_kind
    assert not np.array_equal(a[0].x, c[0].x)
    assert not np.array_equal(a[2][0].q, c[2][0].q)


def test_every_seed_offers_the_same_work():
    (ca, _, [sa, _]), (cb, _, [sb, _]) = _schedules(1), _schedules(2)
    np.testing.assert_array_equal(np.bincount(ca.group), np.bincount(cb.group))
    ga, gb = np.diff(sa.q_due, prepend=0), np.diff(sb.q_due, prepend=0)
    assert len(sa.q_due) == len(sb.q_due) == 24
    np.testing.assert_allclose(np.sort(ga), np.sort(gb))
    assert not np.allclose(ga, gb)
    np.testing.assert_array_equal(sa.w_due, sb.w_due)


def test_a_mix_with_a_query_seed_offers_every_seed_the_same_queries():
    cfg, mix = _cfg("tiny-wiki"), _cfg("tiny-read")
    cfg["corpus"]["seed"] = 11
    mix["queries"]["seed"] = BIG_SEED
    corpus = corpus_mod.make_corpus(cfg, 1)
    book = traffic_mod.start_runbook(corpus)
    a, b = (traffic_mod.make_schedule(mix, corpus, 2.0,
                                      np.random.default_rng([s, 3]), book,
                                      traffic_mod.query_rng(mix, 3))
            for s in (1, BIG_SEED))
    assert not np.array_equal(a.q_base, b.q_base)
    oa, ob = np.lexsort(a.q.T), np.lexsort(b.q.T)
    np.testing.assert_array_equal(a.q[oa], b.q[ob])
    np.testing.assert_array_equal(a.q_base[oa], b.q_base[ob])
    # another stretch of the same mix draws other queries
    c = traffic_mod.make_schedule(mix, corpus, 2.0,
                                  np.random.default_rng([1, 3]), book,
                                  traffic_mod.query_rng(mix, 2))
    assert not np.array_equal(np.sort(c.q_base), np.sort(a.q_base))


def test_poisson_offsets_fill_the_window():
    rng = np.random.default_rng(0)
    due = traffic_mod.poisson_offsets(50.0, 4.0, rng)
    assert len(due) == 200
    assert due[0] > 0.0 and due[-1] < 4.0
    assert np.all(np.diff(due) > 0)


def test_runbook_inserts_in_cluster_order_and_retires_the_oldest():
    corpus, book, scheds = _schedules(3, stretches=(1.0, 2.0))
    kinds = [k for s in scheds for k in s.w_kind]
    lo = np.concatenate([s.w_lo for s in scheds])
    hi = np.concatenate([s.w_hi for s in scheds])
    assert kinds[:4] == ["insert", "delete", "insert", "delete"]
    ins = [(l, h) for k, l, h in zip(kinds, lo, hi) if k == "insert"]
    dels = [(l, h) for k, l, h in zip(kinds, lo, hi) if k == "delete"]
    assert ins[0][0] == corpus.n_resident
    assert all(a[1] == b[0] for a, b in zip(ins, ins[1:]))
    assert dels[0][0] == 0 and all(a[1] == b[0] for a, b in
                                   zip(dels, dels[1:]))
    assert book.hi - book.lo == corpus.n_resident
    assert book.history == list(zip(kinds, lo.tolist(), hi.tolist()))
    # rows are grouped by cluster: inserts arrive cluster by cluster
    assert np.all(np.diff(np.flatnonzero(np.diff(corpus.group))) > 0)


def test_queries_draw_from_rows_resident_at_their_due_time():
    corpus, _, scheds = _schedules(4, stretches=(2.0,))
    s = scheds[0]
    lo0, hi0 = corpus.n_resident * 0, corpus.n_resident
    for i in range(len(s.q_due)):
        j = np.searchsorted(s.w_due, s.q_due[i], side="right")
        lo = lo0 + sum(h - l for k, l, h in zip(s.w_kind[:j], s.w_lo[:j],
                                                 s.w_hi[:j]) if k == "delete")
        hi = hi0 + sum(h - l for k, l, h in zip(s.w_kind[:j], s.w_lo[:j],
                                                 s.w_hi[:j]) if k == "insert")
        assert lo <= s.q_base[i] < hi
        if s.q_fresh[i]:
            last = max(jj for jj in range(j) if s.w_kind[jj] == "insert")
            assert s.w_lo[last] <= s.q_base[i] < s.w_hi[last]
    assert s.q_fresh.any()


def test_seed32_fits_jax_keys():
    assert 0 <= corpus_mod.seed32(BIG_SEED) < 2 ** 32
    assert corpus_mod.seed32(1) != corpus_mod.seed32(2)

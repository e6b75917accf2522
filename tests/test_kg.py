import numpy as np
import pytest

from repro.data import KnowledgeGraph, generate_synthetic_kg, split_kg, TABLE4


def test_dedup_and_sorted():
    tri = np.array([[0, 0, 1], [0, 0, 1], [1, 0, 2], [0, 1, 2]])
    kg = KnowledgeGraph(3, 2, tri)
    assert len(kg) == 3


def test_neighbors():
    tri = np.array([[0, 0, 1], [0, 0, 2], [0, 1, 2], [1, 0, 0]])
    kg = KnowledgeGraph(3, 2, tri)
    assert set(kg.neighbors(0, 0).tolist()) == {1, 2}
    assert set(kg.neighbors(0, 1).tolist()) == {2}
    assert kg.neighbors(2, 0).size == 0


def test_neighbors_of_set():
    tri = np.array([[0, 0, 1], [1, 0, 2], [2, 0, 0]])
    kg = KnowledgeGraph(3, 1, tri)
    out = kg.neighbors_of_set(np.array([0, 1]), 0)
    assert set(out.tolist()) == {1, 2}


def test_incoming_csr():
    tri = np.array([[0, 0, 2], [1, 1, 2], [2, 0, 1]])
    kg = KnowledgeGraph(3, 2, tri)
    indptr, rels, heads = kg.incoming_by_tail
    lo, hi = indptr[2], indptr[3]
    assert sorted(heads[lo:hi].tolist()) == [0, 1]


def test_generator_deterministic():
    a = generate_synthetic_kg(100, 5, 500, seed=7)
    b = generate_synthetic_kg(100, 5, 500, seed=7)
    assert np.array_equal(a.triples, b.triples)
    assert len(a) == 500


def test_generator_power_law(tiny_kg):
    deg = tiny_kg.degree
    # hubby: top decile should hold well over its proportional share
    top = np.sort(deg)[-len(deg) // 10 :].sum()
    assert top > 0.3 * deg.sum()


def test_split_disjoint():
    kg = generate_synthetic_kg(100, 5, 1000, seed=1)
    train, valid, test = split_kg(kg, 0.1, 0.1, seed=0)
    assert len(train) + len(valid) + len(test) == len(kg)
    tr = {tuple(t) for t in train.triples.tolist()}
    for t in valid.tolist() + test.tolist():
        assert tuple(t) not in tr


def test_table4_statistics():
    assert TABLE4["ogbl-wikikg2"].n_entities == 2_500_604
    assert TABLE4["ATLAS-Wiki-Triple-4M"].n_relations == 512_064
    assert TABLE4["FB15k"].n_total == 592_213


def test_full_scale_matches_table4():
    from repro.data import load_dataset

    train, full, stats = load_dataset("FB15k-237", reduced=False)
    assert stats is TABLE4["FB15k-237"]
    for kg in (train, full):
        assert (kg.n_entities, kg.n_relations) == (14_505, 237)
    assert len(train) == stats.n_train == 272_115
    assert len(full) == stats.n_total == 272_115 + 17_526 + 20_438
    # train is a subset of full: the other rows are the valid + test split
    assert full.contains(train.triples).all()

# ---------------------------------------------------------------------------
# Live-write regression suite (DESIGN.md §LiveStore): the four write-path
# bugs plus the snapshot/version surface they unblock.
# ---------------------------------------------------------------------------

def test_dedup_survives_int64_scale():
    """Regression: the old composite dedup key (h*R + r)*E + t overflowed
    int64 just above ATLAS-Wiki-Triple-4M scale, wrapping negative and
    corrupting both dedup and the CSR sort order. lexsort over the columns
    has no composite key to overflow."""
    E, R = 5_000_000, TABLE4["ATLAS-Wiki-Triple-4M"].n_relations
    # Old key for (E-1, R-1, E-1): ((E-1)*R + (R-1))*E + E-1 ≈ 1.28e19
    # > INT64_MAX ≈ 9.22e18 — wraps under the old scheme.
    assert (np.float64(E - 1) * R + (R - 1)) * E + (E - 1) > np.iinfo(np.int64).max
    tri = np.array([
        [E - 1, R - 1, E - 1],
        [E - 1, R - 1, E - 1],   # duplicate of the wrap-prone row
        [E - 1, R - 1, 0],
        [0, 0, 0],
        [0, 0, E - 1],
    ])
    kg = KnowledgeGraph(E, R, tri)
    assert len(kg) == 4
    assert set(kg.neighbors(0, 0).tolist()) == {0, E - 1}
    assert set(kg.neighbors(E - 1, R - 1).tolist()) == {0, E - 1}
    # CSR order: hr strictly non-decreasing, tails sorted within spans.
    hr = kg.triples[:, 0] * R + kg.triples[:, 1]
    assert np.all(np.diff(hr) >= 0)


def test_noop_write_is_free():
    """Regression: add_triples([]) (or an all-duplicates write) used to
    rebuild the CSR, bump the version and flush every listening cache."""
    from repro.core.matcache import MaterializedSubqueryCache

    kg = KnowledgeGraph(4, 2, np.array([[0, 0, 1], [1, 1, 2]]))
    cache = MaterializedSubqueryCache(8)
    cache.watch_kg(kg)
    fired = []

    def listener(reason):
        fired.append(reason)

    kg.add_invalidation_listener(listener)
    cache.insert([("q", 1)], np.ones((1, 4), np.float32))
    assert cache.stats()["live"] == 1
    v0 = kg.version
    kg.add_triples(np.empty((0, 3), np.int64))
    kg.add_triples(np.array([[0, 0, 1]]))               # pure duplicate
    kg.add_triples(np.array([[0, 0, 1], [1, 1, 2]]))    # all duplicates
    assert kg.version == v0
    assert fired == []
    assert cache.stats()["live"] == 1  # warm rows survived the no-ops
    # ...and a real write still invalidates.
    assert len(kg.insert_triples(np.array([[2, 0, 3]]))) == 1
    assert kg.version == v0 + 1
    assert fired == ["kg_write"]
    assert cache.stats()["live"] == 0


def test_failed_write_does_not_bump():
    kg = KnowledgeGraph(4, 2, np.array([[0, 0, 1]]))
    v0 = kg.version
    with pytest.raises(ValueError):
        kg.add_triples(np.array([[9, 0, 1]]))
    with pytest.raises(ValueError):
        kg.add_triples(np.array([[0, 5, 1]]))
    assert kg.version == v0


def test_listener_weakref_no_leak():
    """Regression: listeners were strong refs — a dropped cache stayed
    alive (and kept being notified) forever."""
    import gc
    import weakref

    from repro.core.matcache import MaterializedSubqueryCache

    kg = KnowledgeGraph(4, 2, np.array([[0, 0, 1]]))
    cache = MaterializedSubqueryCache(8)
    cache.watch_kg(kg)
    probe = weakref.ref(cache)
    assert kg.live_listener_count() == 1
    del cache
    gc.collect()
    assert probe() is None          # the KG must not keep the cache alive
    kg.add_triples(np.array([[1, 1, 2]]))  # dead listener must not break writes
    assert kg.live_listener_count() == 0


def test_concurrent_reads_never_torn():
    """Regression: _build reassigned triples/_hr/_tails one-by-one, so a
    lock-free reader could pair the new index with old tails. The adjacency
    now publishes as one immutable tuple; readers either see the whole old
    build or the whole new one."""
    import threading

    kg = KnowledgeGraph(4096, 1, np.array([[0, 0, 1]]))
    stop = threading.Event()
    errors = []

    def reader():
        while not stop.is_set():
            tails = kg.neighbors(0, 0)
            got = set(tails.tolist())
            n = len(got)
            want = set(range(1, n + 1))
            if got != want:                     # torn read: mixed builds
                errors.append((sorted(got), n))
                return

    threads = [threading.Thread(target=reader) for _ in range(4)]
    for t in threads:
        t.start()
    # Writer: monotone frontier — after write k, neighbors(0,0) is exactly
    # {1..k+1}; any other observed set means a torn read.
    for k in range(2, 600):
        kg.add_triples(np.array([[0, 0, k]]))
    stop.set()
    for t in threads:
        t.join()
    assert not errors, errors[:3]


def test_snapshot_pinning_and_retention():
    from repro.data import KGSnapshot, SnapshotUnavailable

    kg = KnowledgeGraph(10, 2, np.array([[0, 0, 1]]), snapshot_retention=3)
    s0 = kg.snapshot()
    assert isinstance(s0, KGSnapshot)
    assert s0.graph_version == kg.graph_version == 0
    kg.add_triples(np.array([[0, 0, 2]]))
    kg.add_triples(np.array([[0, 0, 3]]))
    # Pinned view replays the admitted state regardless of later writes.
    assert set(s0.neighbors(0, 0).tolist()) == {1}
    assert set(kg.snapshot_at(1).neighbors(0, 0).tolist()) == {1, 2}
    assert set(kg.neighbors(0, 0).tolist()) == {1, 2, 3}
    assert kg.retained_versions() == (0, 1, 2)
    kg.add_triples(np.array([[0, 0, 4]]))   # retention=3 evicts version 0
    assert kg.retained_versions() == (1, 2, 3)
    with pytest.raises(SnapshotUnavailable):
        kg.snapshot_at(0)
    # Snapshot arrays are shared, not copied: O(1) snapshots.
    assert kg.snapshot().triples is kg.triples


def test_add_entities():
    kg = KnowledgeGraph(4, 2, np.array([[0, 0, 1]]))
    fired = []

    def listener(reason):
        fired.append(reason)

    kg.add_invalidation_listener(listener)
    v0 = kg.graph_version
    assert kg.add_entities(0) == range(4, 4)
    assert kg.graph_version == v0           # zero-growth is a no-op
    ids = kg.add_entities(3)
    assert ids == range(4, 7)
    assert kg.n_entities == 7 and kg.graph_version == v0 + 1
    assert fired == ["entity_add"]
    kg.add_triples(np.array([[6, 1, 0]]))   # new ids usable immediately
    assert set(kg.neighbors(6, 1).tolist()) == {0}
    assert kg.out_degree.shape == (7,)      # degree views resized


def test_contains():
    kg = KnowledgeGraph(5, 2, np.array([[0, 0, 1], [0, 0, 3], [2, 1, 4]]))
    got = kg.contains(np.array(
        [[0, 0, 1], [0, 0, 2], [0, 0, 3], [2, 1, 4], [2, 0, 4], [4, 1, 2]]))
    assert got.tolist() == [True, False, True, True, False, False]

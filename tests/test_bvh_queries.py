"""BVH queries vs the BruteForce oracle (the paper's own exactness bar:
both indexes must return identical result sets), through the unified
``Index.query()``.

The BVH side of each oracle comparison is pinned to the loop route: at
these sizes the engine's rule would send an unforced batch to brute
force, which is the oracle's own code."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import geometry as G, predicates as P, callbacks as CB
from repro.core.brute_force import BruteForce
from repro.core.bvh import BVH
from repro.core.engine import EngineConfig, QueryEngine
from repro.core.index import ExecutionPolicy

rng = np.random.default_rng(7)
_LOOP = QueryEngine(EngineConfig(force="loop"))


def _loop_bvh(vals):
    """A BVH whose batches all take the while-loop traversal."""
    return BVH(vals, engine=_LOOP)


def _points(n, dim=3, seed=0):
    r = np.random.default_rng(seed)
    return G.Points(jnp.asarray(r.uniform(0, 1, (n, dim)).astype(np.float32)))


@pytest.mark.parametrize("dim", [1, 2, 3, 5, 10])
def test_sphere_counts_match_bruteforce(dim):
    vals = _points(300, dim, seed=dim)
    q = _points(40, dim, seed=100 + dim)
    preds = P.intersects(G.Spheres(q.coords, jnp.full((40,), 0.3)))
    a = _loop_bvh(vals).count(preds)
    b = BruteForce(vals).count(preds)
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_box_query_sets_match():
    vals = _points(400)
    lo = jnp.asarray(rng.uniform(0, 0.8, (30, 3)).astype(np.float32))
    preds = P.intersects(G.Boxes(lo, lo + 0.2))
    ra = _loop_bvh(vals).query(preds)
    rb = BruteForce(vals).query(preds)
    ia, oa = np.asarray(ra.indices), np.asarray(ra.offsets)
    ib, ob = np.asarray(rb.indices), np.asarray(rb.offsets)
    assert np.array_equal(oa, ob)
    for q in range(30):
        assert set(ia[oa[q]:oa[q + 1]].tolist()) \
            == set(ib[ob[q]:ob[q + 1]].tolist())


@pytest.mark.parametrize("k", [1, 4, 17])
def test_knn_matches_bruteforce(k):
    vals = _points(500)
    q = _points(64, seed=5)
    preds = P.nearest(q, k=k)
    ra = _loop_bvh(vals).query(preds)
    rb = BruteForce(vals).query(preds)
    assert np.allclose(np.asarray(ra.distances), np.asarray(rb.distances),
                       atol=1e-5)
    # kNN results also gather the matched values ((Q, k, ...))
    assert ra.values.coords.shape == (64, k, 3)


def test_knn_against_triangles_fine_distance():
    """§2.1.2 fine nearest: distances to the triangles, not their boxes."""
    r = np.random.default_rng(11)
    a = r.uniform(0, 1, (200, 3)).astype(np.float32)
    tris = G.Triangles(jnp.asarray(a),
                       jnp.asarray(a + r.uniform(-.1, .1, (200, 3)).astype(np.float32)),
                       jnp.asarray(a + r.uniform(-.1, .1, (200, 3)).astype(np.float32)))
    q = _points(32, seed=12)
    preds = P.nearest(q, k=3)
    da = _loop_bvh(tris).query(preds).distances
    db = BruteForce(tris).query(preds).distances
    assert np.allclose(np.asarray(da), np.asarray(db), atol=1e-5)


def test_degenerate_sizes():
    for n in (0, 1):
        vals = _points(max(n, 1), seed=20)
        if n == 0:
            vals = G.Points(jnp.zeros((0, 3), jnp.float32))
        bvh = BVH(vals)
        assert bvh.size() == n and bvh.empty() == (n == 0)
        q = _points(4, seed=21)
        c = bvh.count(P.intersects(G.Spheres(q.coords, jnp.full((4,), 10.0))))
        assert np.all(np.asarray(c) == n)


def test_query_out_transforms_values():
    """Query flavor (2): output type differs from Value (§2.1.3)."""
    vals = _points(100)
    q = _points(10, seed=30)
    preds = P.intersects(G.Spheres(q.coords, jnp.full((10,), 0.4)))
    bvh = BVH(vals)

    def out_fn(pred, value, index, t):
        return jnp.sum(value.coords)            # scalar per match

    res = bvh.query(preds, out=out_fn)
    ref = bvh.query(preds)
    assert np.array_equal(np.asarray(res.offsets), np.asarray(ref.offsets))
    expect = np.asarray(vals.coords).sum(1)[np.asarray(ref.indices)]
    assert np.allclose(np.asarray(res.values), expect, atol=1e-5)


def test_attach_data_reaches_callback():
    """ArborX::attach: per-predicate payload delivered to callbacks."""
    vals = _points(50)
    q = _points(8, seed=31)
    payload = jnp.arange(8, dtype=jnp.float32) * 10
    preds = P.attach_data(
        P.intersects(G.Spheres(q.coords, jnp.full((8,), 0.5))), payload)

    def cb(state, pred, value, index, t):
        return jnp.maximum(state, pred.data), jnp.bool_(False)

    got = BVH(vals).query(preds, callback=(cb, jnp.float32(-1.0)))
    counts = BVH(vals).count(
        P.intersects(G.Spheres(q.coords, jnp.full((8,), 0.5))))
    expect = np.where(np.asarray(counts) > 0, np.asarray(payload), -1.0)
    assert np.allclose(np.asarray(got), expect)


@given(st.sampled_from([2, 3, 17, 128]), st.integers(0, 100000),
       st.floats(0.05, 0.6), st.sampled_from([2, 3]))
@settings(max_examples=12, deadline=None)
def test_property_bvh_equals_bruteforce(n, seed, radius, dim):
    """The system invariant: BVH(X).query == BruteForce(X).query for any
    point set and radius (hypothesis-driven)."""
    r = np.random.default_rng(seed)
    vals = G.Points(jnp.asarray(r.uniform(0, 1, (n, dim)).astype(np.float32)))
    q = G.Points(jnp.asarray(r.uniform(0, 1, (8, dim)).astype(np.float32)))
    preds = P.intersects(G.Spheres(q.coords,
                                   jnp.full((8,), np.float32(radius))))
    a = _loop_bvh(vals).count(preds)
    b = BruteForce(vals).count(preds)
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_csr_zero_total_matches():
    """All-miss predicates: empty CSR arrays, all-zero offsets — on both
    indexes and on every engine route (`_csr_pack` with total == 0)."""
    from repro.core.engine import EngineConfig, QueryEngine
    vals = _points(50, seed=50)
    far = jnp.asarray(rng.uniform(10, 11, (6, 3)).astype(np.float32))
    preds = P.intersects(G.Spheres(far, jnp.full((6,), 0.01, jnp.float32)))
    for force in ("loop", "bruteforce"):
        eng = QueryEngine(EngineConfig(force=force))
        res = BVH(vals, engine=eng).query(preds)
        assert res.indices.shape == (0,)
        assert res.values.coords.shape == (0, 3)
        assert np.array_equal(np.asarray(res.offsets), np.zeros(7, np.int32))
    res = BruteForce(vals).query(preds)
    assert res.indices.shape == (0,)
    assert np.array_equal(np.asarray(res.offsets), np.zeros(7, np.int32))


def test_csr_capacity_clamping():
    """max_doublings=0 pins the raw truncation contract: offsets cumsum the
    CLAMPED counts, every stored slice is a subset of the true match set,
    and the result carries overflow=True."""
    from repro.core.engine import EngineConfig, QueryEngine
    vals = _points(60, seed=51)
    preds = P.intersects(G.Spheres(vals.coords[:5], jnp.full((5,), 10.0)))
    full = np.asarray(BruteForce(vals).count(preds))
    assert (full == 60).all()
    cap = 7
    pol = ExecutionPolicy(max_doublings=0)
    for force in ("loop", "bruteforce"):
        eng = QueryEngine(EngineConfig(force=force))
        res = BVH(vals, engine=eng).query(preds, capacity=cap,
                                          policy=pol.override(engine=eng))
        assert res.overflow
        off = np.asarray(res.offsets)
        assert np.array_equal(off, np.arange(6) * cap)
        idx = np.asarray(res.indices)
        assert idx.shape == (5 * cap,)
        for qi in range(5):
            s = set(idx[off[qi]:off[qi + 1]].tolist())
            assert len(s) == cap and s <= set(range(60))


def test_csr_capacity_overflow_doubling_retry():
    """A low capacity guess no longer truncates silently: the fill is
    retried at doubled capacity until the true max count fits, and the
    result unpacks like a plain NamedTuple with overflow=False."""
    from repro.core.engine import EngineConfig, QueryEngine
    vals = _points(60, seed=51)
    preds = P.intersects(G.Spheres(vals.coords[:5], jnp.full((5,), 10.0)))
    for force in ("loop", "bruteforce"):
        eng = QueryEngine(EngineConfig(force=force))
        res = BVH(vals, engine=eng).query(preds, capacity=7)
        v, idx, off, dists, overflow = res          # NamedTuple unpacking
        assert not overflow and dists is None
        off = np.asarray(off)
        assert np.array_equal(off, np.arange(6) * 60)   # full result sets
        for qi in range(5):
            assert set(np.asarray(idx[off[qi]:off[qi + 1]]).tolist()) \
                == set(range(60))


def test_csr_capacity_retry_cap_flags_overflow():
    """The retry is capped: with max_doublings=1 a 7 -> 14 bump cannot fit
    60 matches, so the result stays truncated (at the doubled width) and
    is flagged."""
    vals = _points(60, seed=51)
    preds = P.intersects(G.Spheres(vals.coords[:5], jnp.full((5,), 10.0)))
    res = BVH(vals).query(preds, capacity=7,
                          policy=ExecutionPolicy(capacity=7, max_doublings=1))
    assert res.overflow
    assert np.array_equal(np.asarray(res.offsets), np.arange(6) * 14)


def test_csr_empty_predicate_batch():
    """Q == 0: query must return empty CSR arrays, not crash sizing the
    capacity from an empty counts reduction."""
    vals = _points(50, seed=53)
    preds = P.intersects(G.Spheres(jnp.zeros((0, 3), jnp.float32),
                                   jnp.zeros((0,), jnp.float32)))
    res = BVH(vals).query(preds)
    assert res.indices.shape == (0,)
    assert np.array_equal(np.asarray(res.offsets), np.zeros(1, np.int32))
    assert BVH(vals).count(preds).shape == (0,)


def test_csr_degenerate_trees():
    """N in {0, 1}: no LBVH exists; count/query/knn run the linear-scan
    fallback and keep the CSR layout contract."""
    q = _points(3, seed=52)
    preds = P.intersects(G.Spheres(q.coords, jnp.full((3,), 10.0)))
    for n in (0, 1):
        vals = G.Points(jnp.zeros((n, 3), jnp.float32))
        bvh = BVH(vals)
        assert bvh.tree is None
        c = np.asarray(bvh.count(preds))
        assert (c == n).all()
        res = bvh.query(preds)
        assert np.array_equal(np.asarray(res.offsets), np.arange(4) * n)
        assert res.indices.shape == (3 * n,)
        kres = bvh.query(P.nearest(q, k=2))
        d, i = np.asarray(kres.distances), np.asarray(kres.indices)
        assert (i[:, n:] == -1).all() and np.isinf(d[:, n:]).all()
        if n == 1:
            assert (i[:, 0] == 0).all() and np.isfinite(d[:, 0]).all()


def test_early_exit_prunes_traversal():
    """§2.6 bullet 5: count_with_limit(1) must stop at the first match."""
    vals = _points(1000)
    q = _points(16, seed=40)
    preds = P.intersects(G.Spheres(q.coords, jnp.full((16,), 0.5)))
    bvh = BVH(vals)
    got = bvh.query(preds, callback=CB.count_with_limit(1))
    full = bvh.count(preds)
    assert np.all(np.asarray(got) <= 1)
    assert np.array_equal(np.asarray(got) > 0, np.asarray(full) > 0)

"""QueryEngine dispatch, and both routes (brute force and the while loop)
against numpy all-pairs oracles."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine as E
from repro.core import geometry as G, predicates as P
from repro.core.bvh import BVH
from repro.core.engine import (ROUTE_BRUTEFORCE, ROUTE_LOOP, EngineConfig,
                               QueryEngine)
from repro.core.lbvh import build
from repro.core.traversal import traverse
from repro.core import callbacks as CB

rng = np.random.default_rng(17)

ROUTES = pytest.mark.parametrize("route", [ROUTE_LOOP, ROUTE_BRUTEFORCE])


def _pts(n, dim=3, seed=0, lo=0.0, hi=1.0):
    r = np.random.default_rng(seed)
    return jnp.asarray(r.uniform(lo, hi, (n, dim)).astype(np.float32))


def _forced(values, route):
    eng = QueryEngine(EngineConfig(force=route))
    return BVH(values, engine=eng), eng


def _np_dist(a, b):
    """(len(a), len(b)) float64 euclidean distances."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return np.sqrt(((a[:, None, :] - b[None, :, :]) ** 2).sum(-1))


def _np_box_dist(p, lo, hi):
    """(len(p), len(lo)) float64 distance from each point to each box."""
    p = np.asarray(p, np.float64)[:, None, :]
    lo = np.asarray(lo, np.float64)[None]
    hi = np.asarray(hi, np.float64)[None]
    gap = np.maximum(np.maximum(lo - p, p - hi), 0.0)
    return np.sqrt((gap ** 2).sum(-1))


def _csr_sets(res):
    idx, off = np.asarray(res.indices), np.asarray(res.offsets)
    return [set(idx[off[i]:off[i + 1]].tolist()) for i in range(len(off) - 1)]


# ---------------------------------------------------------------------------
# both routes vs the all-pairs oracle: spatial
# ---------------------------------------------------------------------------

@ROUTES
@pytest.mark.parametrize("n,q,dim", [(64, 16, 2), (300, 40, 3), (513, 33, 5)])
def test_spatial_sphere_counts_bit_exact(n, q, dim, route):
    pts = _pts(n, dim, seed=n)
    qp = _pts(q, dim, seed=1000 + n)
    r = jnp.full((q,), 0.3, jnp.float32)
    preds = P.intersects(G.Spheres(qp, r))
    bvh, eng = _forced(G.Points(pts), route)
    assert eng.route_spatial(bvh, preds) == route
    want = (_np_dist(qp, pts) <= 0.3).sum(-1)
    assert np.array_equal(np.asarray(bvh.count(preds)), want)


@ROUTES
@pytest.mark.parametrize("kind", ["point", "box", "sphere"])
def test_spatial_all_query_kinds_vs_oracle(kind, route):
    """Counts AND match sets identical to the oracle for point, box and
    sphere queries over Boxes values."""
    r0 = np.random.default_rng(3)
    lo = jnp.asarray(r0.uniform(0, 1, (200, 3)).astype(np.float32))
    hi = lo + jnp.asarray(r0.uniform(0.01, 0.2, (200, 3)).astype(np.float32))
    q = 48
    qp = _pts(q, 3, seed=4)
    lo_n, hi_n, qp_n = (np.asarray(a) for a in (lo, hi, qp))
    if kind == "point":
        preds = P.intersects(G.Points(qp))
        want = ((lo_n[None] <= qp_n[:, None]) & (qp_n[:, None] <= hi_n[None])
                ).all(-1)
    elif kind == "box":
        preds = P.intersects(G.Boxes(qp, qp + 0.25))
        q_hi = np.asarray(qp + 0.25)
        want = ((qp_n[:, None] <= hi_n[None]) & (lo_n[None] <= q_hi[:, None])
                ).all(-1)
    else:
        preds = P.intersects(G.Spheres(qp, jnp.full((q,), 0.2, jnp.float32)))
        want = _np_box_dist(qp_n, lo_n, hi_n) <= 0.2
    bvh, eng = _forced(G.Boxes(lo, hi), route)
    assert eng.route_spatial(bvh, preds) == route
    assert np.array_equal(np.asarray(bvh.count(preds)), want.sum(-1))
    assert _csr_sets(bvh.query(preds)) == \
        [set(np.flatnonzero(row).tolist()) for row in want]


@ROUTES
def test_spatial_capacity_clamps_but_counts_full(route):
    pts = _pts(400, 3, seed=9)
    qp = _pts(32, 3, seed=10)
    r = jnp.full((32,), 0.4, jnp.float32)
    preds = P.intersects(G.Spheres(qp, r))
    bvh, eng = _forced(G.Points(pts), route)
    match = _np_dist(qp, pts) <= 0.4
    (cnt, buf), info = eng.exec_spatial(bvh, preds, 5)
    assert info.route == route
    assert np.array_equal(np.asarray(cnt), match.sum(-1))
    buf = np.asarray(buf)
    stored = (buf >= 0).sum(1)
    assert np.array_equal(stored, np.minimum(match.sum(-1), 5))
    for i in range(32):
        assert set(buf[i, :stored[i]].tolist()) <= \
            set(np.flatnonzero(match[i]).tolist())


def test_pair_traversal_min_pos_vs_oracle():
    """The range_last position filter (§2.6 pair traversal) of the loop:
    with each query's own sorted position as ``min_pos``, a self-join
    counts exactly the matches at later sorted positions. Brute force has
    no sorted positions, so this case runs on the loop alone."""
    pts = _pts(128, 3, seed=11)
    tree = build(G.Boxes(pts, pts))
    # self-join: every point queries a sphere around itself
    r = jnp.full((128,), 0.25, jnp.float32)
    preds = P.intersects(G.Spheres(pts, r))
    # min_pos = own sorted position -> strict upper-triangle join
    inv_perm = np.zeros((128,), np.int32)
    inv_perm[np.asarray(tree.leaf_perm)] = np.arange(128, dtype=np.int32)
    cb, s0 = CB.counting()
    s0 = jnp.broadcast_to(s0, (128,))
    cnt = np.asarray(traverse(tree, G.Points(pts), preds, cb, s0,
                              min_pos=jnp.asarray(inv_perm)))
    match = _np_dist(pts, pts) <= 0.25
    later = inv_perm[None, :] > inv_perm[:, None]
    assert np.array_equal(cnt, (match & later).sum(-1))
    # upper-triangle invariant: sum == (total pairs - Q self matches) / 2
    assert int(cnt.sum()) == (int(match.sum()) - 128) // 2


# ---------------------------------------------------------------------------
# both routes vs the all-pairs oracle: kNN
# ---------------------------------------------------------------------------

@ROUTES
@pytest.mark.parametrize("n,q,dim,k", [(64, 16, 2, 1), (500, 64, 3, 8),
                                       (513, 33, 5, 4), (100, 8, 3, 17)])
def test_knn_vs_oracle(n, q, dim, k, route):
    pts = _pts(n, dim, seed=n + 1)
    qp = _pts(q, dim, seed=2000 + n)
    preds = P.nearest(G.Points(qp), k=k)
    bvh, eng = _forced(G.Points(pts), route)
    assert eng.route_knn(bvh, preds) == route
    res = bvh.query(preds)
    d1, i1 = np.asarray(res.distances), np.asarray(res.indices)
    d1, i1 = d1.reshape(q, k), i1.reshape(q, k)
    dist = _np_dist(qp, pts)
    assert np.allclose(d1, np.sort(dist, axis=-1)[:, :k], atol=1e-4)
    # each returned index is at its returned distance: indices may differ
    # from the oracle's only across exact-distance ties
    assert np.allclose(np.take_along_axis(dist, i1, 1), d1, atol=1e-4)


@ROUTES
def test_knn_k_exceeds_n_pads_with_inf(route):
    pts = _pts(8, 3, seed=5)
    preds = P.nearest(G.Points(_pts(4, 3, seed=6)), k=12)
    bvh, eng = _forced(G.Points(pts), route)
    assert eng.route_knn(bvh, preds) == route
    d, i = bvh._knn_impl(preds, bvh.policy)
    d, i = np.asarray(d), np.asarray(i)
    assert (i[:, :8] >= 0).all() and (i[:, 8:] == -1).all()
    assert np.isinf(d[:, 8:]).all()
    assert (np.diff(d[:, :8], axis=1) >= 0).all()      # sorted ascending


# ---------------------------------------------------------------------------
# engine routing
# ---------------------------------------------------------------------------

def _mk(n=600, engine=None):
    return BVH(G.Points(_pts(n, 3, seed=42)), engine=engine)


def test_bf_max_work_is_the_chip_crossover():
    """The crossover the chip ran at (a batch of 1024 queries takes brute
    force up to 4096 points)."""
    assert E.BF_MAX_WORK == 1 << 22


def test_route_small_work_goes_bruteforce():
    eng = QueryEngine()
    bvh = _mk(600, eng)
    preds = P.intersects(G.Spheres(_pts(10, 3, seed=1), jnp.full((10,), 0.1)))
    assert eng.route_spatial(bvh, preds) == ROUTE_BRUTEFORCE
    knn = P.nearest(G.Points(_pts(10, 3, seed=2)), k=4)
    assert eng.route_knn(bvh, knn) == ROUTE_BRUTEFORCE


def test_route_large_work_goes_loop():
    """N·Q at BF_MAX_WORK still takes brute force; one query more takes
    the loop, for both ops."""
    eng = QueryEngine()
    bvh = _mk(1024, eng)
    at = E.BF_MAX_WORK // 1024
    for q, want in ((at, ROUTE_BRUTEFORCE), (at + 1, ROUTE_LOOP)):
        qp = _pts(q, 3, seed=q)
        spatial = P.intersects(G.Spheres(qp, jnp.full((q,), 0.1)))
        assert eng.route_spatial(bvh, spatial) == want
        assert eng.route_knn(bvh, P.nearest(G.Points(qp), k=4)) == want


def test_route_ineligible_values_fall_back_to_loop():
    """Value geometry does not enter the rule: a Triangles index routes
    Intersects batches by N·Q like a Points one, and what brute force
    cannot answer over it (ray predicates) takes the loop, forced or not."""
    r = np.random.default_rng(2)
    a = jnp.asarray(r.uniform(0, 1, (64, 3)).astype(np.float32))
    tris = G.Triangles(a, a + 0.05, a + 0.1)
    preds = P.intersects(G.Spheres(_pts(32, 3, seed=3), jnp.full((32,), 0.2)))
    rays = P.RayIntersect(G.Rays(_pts(8, 3, seed=4), _pts(8, 3, seed=5)))
    for force, spatial in ((None, ROUTE_BRUTEFORCE),
                           (ROUTE_BRUTEFORCE, ROUTE_BRUTEFORCE),
                           (ROUTE_LOOP, ROUTE_LOOP)):
        eng = QueryEngine(EngineConfig(force=force))
        bvh = BVH(tris, engine=eng)
        assert eng.route_spatial(bvh, preds) == spatial
        assert eng.route_spatial(bvh, rays) == ROUTE_LOOP


def test_route_ray_predicates_always_loop():
    for force in (None, ROUTE_BRUTEFORCE):
        eng = QueryEngine(EngineConfig(force=force))
        bvh = _mk(600, eng)
        rays = P.RayNearest(G.Rays(_pts(8, 3, seed=4), _pts(8, 3, seed=5)), 1)
        assert eng.route_spatial(bvh, rays) == ROUTE_LOOP
        assert eng.route_knn(bvh, rays) == ROUTE_LOOP


def test_route_force_override():
    for force in (ROUTE_BRUTEFORCE, ROUTE_LOOP):
        eng = QueryEngine(EngineConfig(force=force))
        bvh = _mk(600, eng)
        preds = P.intersects(G.Spheres(_pts(16, 3, seed=6),
                                       jnp.full((16,), 0.1)))
        assert eng.route_spatial(bvh, preds) == force
    with pytest.raises(ValueError, match="force='pallas'"):
        EngineConfig(force="pallas")


# ---------------------------------------------------------------------------
# end-to-end: BVH results are identical on every route
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("force", [ROUTE_LOOP, ROUTE_BRUTEFORCE])
def test_bvh_query_results_path_independent(force):
    vals = G.Points(_pts(300, 3, seed=7))
    preds = P.intersects(G.Spheres(_pts(24, 3, seed=8),
                                   jnp.full((24,), 0.25, jnp.float32)))
    ref_bvh = BVH(vals, engine=QueryEngine(EngineConfig(force=ROUTE_LOOP)))
    bvh = BVH(vals, engine=QueryEngine(EngineConfig(force=force)))
    assert np.array_equal(np.asarray(bvh.count(preds)),
                          np.asarray(ref_bvh.count(preds)))
    ra, rb = bvh.query(preds), ref_bvh.query(preds)
    ia, oa = ra.indices, ra.offsets
    ib, ob = rb.indices, rb.offsets
    assert np.array_equal(np.asarray(oa), np.asarray(ob))
    ia, ib, oa = np.asarray(ia), np.asarray(ib), np.asarray(oa)
    for i in range(24):
        assert set(ia[oa[i]:oa[i + 1]].tolist()) == set(ib[oa[i]:oa[i + 1]].tolist())

    knn = P.nearest(G.Points(_pts(24, 3, seed=9)), k=5)
    da = bvh.query(knn).distances
    db = ref_bvh.query(knn).distances
    assert np.allclose(np.asarray(da), np.asarray(db), atol=1e-4)


# ---------------------------------------------------------------------------
# executable-cache / stats atomicity (ISSUE 8 satellite)
# ---------------------------------------------------------------------------

def test_counters_and_lru_exact_under_two_threads():
    """jit_traces and the cache hit/miss/LRU bookkeeping are guarded by
    _cache_lock: two threads hammering the same engine must land EXACT
    totals (an unlocked `+=` loses increments under interleaving) and the
    LRU must hold exactly max_executables entries."""
    import threading

    eng = QueryEngine(EngineConfig(max_executables=4))
    keys = [("k", i) for i in range(16)]
    rounds = 500
    barrier = threading.Barrier(2)

    def hammer():
        barrier.wait()
        for r in range(rounds):
            eng._note_trace()
            key = keys[r % len(keys)]
            fn, _ = eng._cached(key, lambda key=key: ("exe", key))
            assert fn == ("exe", key)

    ts = [threading.Thread(target=hammer) for _ in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(120)

    s = eng.stats.snapshot()
    assert s.jit_traces == 2 * rounds                 # exact, no lost updates
    assert s.cache_hits + s.cache_misses == 2 * rounds
    # 16 keys cycling through a 4-slot LRU: every lookup re-compiles, but
    # the count is exact either way and the LRU bound holds
    assert len(eng._executables) == 4
    assert s.cache_misses >= 16


def test_warm_dispatch_counts_exact_across_threads():
    """End-to-end: after a warmup dispatch, concurrent exec_knn calls from
    two threads are all cache hits and never retrace."""
    import threading

    pts = _pts(64, 3, seed=5)
    bvh = BVH(G.Points(pts))
    eng = bvh.policy.engine if bvh.policy.engine else QueryEngine()
    preds = P.nearest(G.Points(_pts(8, 3, seed=6)), k=2)
    eng.exec_knn(bvh, preds)                          # warm: 1 miss, 1 trace
    base = eng.stats.snapshot()
    assert base.cache_misses >= 1 and base.jit_traces >= 1

    per_thread = 16
    barrier = threading.Barrier(2)

    def serve():
        barrier.wait()
        for _ in range(per_thread):
            (d, i), info = eng.exec_knn(bvh, preds)
            assert info.cache_hit

    ts = [threading.Thread(target=serve) for _ in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(300)

    s = eng.stats.snapshot()
    assert s.cache_hits == base.cache_hits + 2 * per_thread
    assert s.cache_misses == base.cache_misses        # nothing recompiled
    assert s.jit_traces == base.jit_traces            # nothing retraced

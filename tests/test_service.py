"""QueryServer / Batcher / engine executable cache (DESIGN.md §5).

The headline contract: after warming the power-of-two buckets, 100 mixed-
shape requests trigger ZERO recompiles — asserted by counting actual jit
traces (each cached executable bumps a counter from inside its traced
body, so the counter moves only when XLA retraces).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import geometry as G, predicates as P
from repro.core.bvh import BVH
from repro.core.engine import (ROUTE_BRUTEFORCE, ROUTE_LOOP,
                               EngineConfig, QueryEngine)
from repro.service import (QueryServer, ServiceConfig, knn_request,
                           ray_request, within_request)
from repro.service.batcher import Batcher, bucket_size

DIM = 3


def _pts(n, seed=0):
    return np.random.default_rng(seed).uniform(
        0, 1, (n, DIM)).astype(np.float32)


def _server(n=500, seed=1, capacity=32, config=None, engine=None):
    srv = QueryServer(engine=engine,
                      config=config or ServiceConfig(capacity=capacity))
    srv.create_index("default", G.Points(jnp.asarray(_pts(n, seed))))
    return srv


# ---------------------------------------------------------------------------
# batcher
# ---------------------------------------------------------------------------

def test_bucket_size_power_of_two():
    assert [bucket_size(q) for q in (1, 7, 8, 9, 100, 128)] \
        == [8, 8, 8, 16, 128, 128]
    assert bucket_size(3, min_bucket=4) == 4


def test_batcher_groups_by_kind_k_and_pads():
    b = Batcher(min_bucket=8)
    reqs = [knn_request(_pts(5, 1), k=4), knn_request(_pts(6, 2), k=4),
            knn_request(_pts(3, 3), k=2), within_request(_pts(9, 4), 0.1),
            ray_request(_pts(2, 5), np.ones((2, DIM), np.float32))]
    groups = b.plan(reqs)
    assert len(groups) == 4          # knn k=4, knn k=2, within, ray
    by_kind = {(g.kind, g.k): g for g in groups}
    g = by_kind[("knn", 4)]
    assert (g.n_real, g.bucket, g.a.shape) == (11, 16, (16, DIM))
    assert [(rid, m) for rid, _, m in g.members] == [(0, 5), (1, 6)]
    # padding repeats the last real row
    assert np.array_equal(g.a[11:], np.repeat(g.a[10:11], 5, 0))
    assert by_kind[("within", 0)].bucket == 16
    assert by_kind[("ray", 1)].bucket == 8


def test_unknown_kind_rejected_at_plan_time_with_named_set():
    """A request whose kind dodged construction-time validation must fail
    at enqueue with an error naming the kind AND the supported set — not
    as an opaque shape error inside a later dispatch."""
    from repro.service.batcher import Request
    bogus = object.__new__(Request)
    for field, val in (("kind", "voxel"), ("a", _pts(3, 1)), ("b", None),
                       ("k", 1), ("index", "default")):
        object.__setattr__(bogus, field, val)
    with pytest.raises(ValueError, match=r"voxel.*knn.*within.*ray"):
        Batcher().plan([bogus])
    with pytest.raises(ValueError, match=r"voxel.*knn.*within.*ray"):
        Request("voxel", _pts(3, 1))


def test_batcher_rejects_bad_requests():
    with pytest.raises(ValueError, match="kind"):
        knn_request(_pts(3, 1), k=1).__class__(
            "nope", _pts(3, 1))
    with pytest.raises(ValueError, match="empty"):
        knn_request(np.zeros((0, DIM), np.float32))
    from repro.service.batcher import Request
    with pytest.raises(ValueError, match="mismatch"):
        Request("within", _pts(5, 2), np.full((3,), 0.1, np.float32))
    with pytest.raises(ValueError, match="power of two"):
        Batcher(min_bucket=6)


# ---------------------------------------------------------------------------
# server results == direct BVH queries
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["knn", "within", "ray"])
def test_execute_group_is_launch_then_finish(kind):
    """``execute_group`` is ``launch_group`` then ``finish_group``: the
    launch hands back device arrays without waiting, the finish copies
    them back and scatters the same Responses."""
    import jax

    from repro.service.server import (execute_group, finish_group,
                                      launch_group)
    srv = _server(300, seed=12, capacity=8)
    qs = [_pts(5, 13), _pts(9, 14)]
    dirs = np.random.default_rng(15).normal(size=(9, DIM))
    reqs = {"knn": [knn_request(q, k=3) for q in qs],
            "within": [within_request(q, 0.2) for q in qs],
            "ray": [ray_request(qs[0], dirs[:5], k=2),
                    ray_request(qs[1], dirs, k=2)]}[kind]
    (group,) = srv.batcher.plan(reqs)
    with srv.store.pinned("default") as entry:
        execute_group(srv.engine, srv.config, entry, group)    # warm
        launched = launch_group(srv.engine, srv.config, entry, group)
        assert all(isinstance(x, jax.Array) for x in launched.arrays)
        a = finish_group(launched)
        b = execute_group(srv.engine, srv.config, entry, group)
    assert sorted(a) == sorted(b) == [0, 1]
    for rid in a:
        assert a[rid].stats == b[rid].stats == launched.stats
        assert a[rid].overflow == b[rid].overflow
        for field in ("dists", "idxs", "counts"):
            x, y = getattr(a[rid], field), getattr(b[rid], field)
            assert (x is None and y is None) or np.array_equal(x, y)
    if kind == "within":
        assert a[1].counts.shape == (9,) and a[1].idxs.shape == (9, 8)
    else:
        assert a[1].dists.shape == (9, 3 if kind == "knn" else 2)


def test_server_scatter_matches_direct_queries():
    pts = _pts(400, seed=2)
    srv = QueryServer(config=ServiceConfig(capacity=64))
    srv.create_index("default", G.Points(jnp.asarray(pts)))
    bvh = BVH(G.Points(jnp.asarray(pts)))

    qa, qb, qc = _pts(5, 3), _pts(11, 4), _pts(7, 5)
    dirs = np.random.default_rng(6).normal(size=(7, DIM)).astype(np.float32)
    rs = srv.handle([knn_request(qa, k=3), within_request(qb, 0.2),
                     ray_request(qc, dirs, k=2)])

    kr = bvh.query(P.nearest(G.Points(jnp.asarray(qa)), k=3))
    d, i = kr.distances, kr.indices
    assert np.allclose(rs[0].dists, np.asarray(d), atol=1e-6)
    assert np.array_equal(rs[0].idxs, np.asarray(i))

    want = bvh.count(P.intersects(
        G.Spheres(jnp.asarray(qb), jnp.full((11,), 0.2, jnp.float32))))
    assert np.array_equal(rs[1].counts, np.asarray(want))
    assert not rs[1].overflow
    for row, c in zip(rs[1].idxs, rs[1].counts):
        assert (row[:c] >= 0).all() and (row[c:] == -1).all()

    from repro.core import raytracing as RT
    t, ri = RT.cast_nearest(bvh, G.Rays(jnp.asarray(qc), jnp.asarray(dirs)),
                            k=2)
    assert np.allclose(rs[2].dists, np.asarray(t), atol=1e-6)

    # stats populated
    for r, kind in zip(rs, ("knn", "within", "ray")):
        assert r.stats.kind == kind
        assert r.stats.route in (ROUTE_BRUTEFORCE, ROUTE_LOOP)
        assert r.stats.bucket == bucket_size(len(r.dists if r.counts is None
                                                else r.counts))
        assert (r.stats.index_name, r.stats.index_version) == ("default", 1)
    assert rs[2].stats.route == ROUTE_LOOP      # rays always take the loop


def test_server_within_overflow_flagged_per_request():
    pts = _pts(60, seed=7)
    srv = QueryServer(config=ServiceConfig(capacity=4))
    srv.create_index("default", G.Points(jnp.asarray(pts)))
    # one request that spills (r=10 matches all 60), one that can't (r=0)
    rs = srv.handle([within_request(_pts(3, 8), 10.0),
                     within_request(_pts(3, 9) + 50.0, 1e-6)])
    assert rs[0].overflow and (rs[0].counts == 60).all()
    assert not rs[1].overflow and (rs[1].counts == 0).all()


def test_server_serves_updated_index_version():
    pts = _pts(300, seed=10)
    srv = _server(300, seed=10)
    r0 = srv.handle([knn_request(_pts(4, 11), k=2)])[0]
    assert r0.stats.index_version == 1
    srv.update_index("default", G.Points(jnp.asarray(pts + 0.001)))
    r1 = srv.handle([knn_request(_pts(4, 11), k=2)])[0]
    assert r1.stats.index_version == 2
    # same bucket shape + same N -> the refit swap reuses the warm executable
    assert r1.stats.cache_hit


# ---------------------------------------------------------------------------
# zero recompiles after warmup (the acceptance criterion)
# ---------------------------------------------------------------------------

def test_zero_recompiles_after_warmup_across_100_mixed_requests():
    rng = np.random.default_rng(12)
    srv = _server(500, seed=12, capacity=16)
    srv.warmup("default", [("knn", 8), ("within", 0), ("ray", 1)],
               max_bucket=128, dim=DIM)
    stats = srv.engine.stats
    assert stats.jit_traces == stats.cache_misses > 0

    before = stats.snapshot()
    served = 0
    for _ in range(25):                      # 25 calls x 4 requests = 100
        m = [int(rng.integers(1, 65)) for _ in range(4)]
        reqs = [knn_request(rng.uniform(0, 1, (m[0], DIM)), k=8),
                within_request(rng.uniform(0, 1, (m[1], DIM)), 0.1),
                knn_request(rng.uniform(0, 1, (m[2], DIM)), k=8),
                ray_request(rng.uniform(0, 1, (m[3], DIM)),
                            rng.normal(size=(m[3], DIM)))]
        for r in srv.handle(reqs):
            assert r.stats.cache_hit
            served += 1
    assert served == 100
    after = srv.engine.stats
    assert after.jit_traces == before.jit_traces       # ZERO recompiles
    assert after.cache_misses == before.cache_misses
    assert after.cache_hits > before.cache_hits


def test_exec_cache_keys_split_by_route_and_shape():
    """Distinct (route, bucket) pairs compile distinct executables; the
    same pair is reused."""
    eng = QueryEngine(EngineConfig(force="loop"))
    srv = QueryServer(engine=eng, config=ServiceConfig(capacity=8))
    srv.create_index("default", G.Points(jnp.asarray(_pts(300, 13))))
    srv.handle([within_request(_pts(5, 14), 0.1)])    # bucket 8
    srv.handle([within_request(_pts(20, 15), 0.1)])   # bucket 32
    assert eng.stats.cache_misses == 2
    srv.handle([within_request(_pts(6, 16), 0.1)])    # bucket 8 again
    assert eng.stats.cache_misses == 2
    assert eng.stats.cache_hits == 1


def test_exec_paths_agree_across_forced_routes():
    """The same bucket served by both routes returns identical counts and
    match sets (DESIGN.md §3 invariant, now through the service)."""
    pts = _pts(400, seed=17)
    q = _pts(24, 18)
    results = {}
    for force in (ROUTE_LOOP, ROUTE_BRUTEFORCE):
        eng = QueryEngine(EngineConfig(force=force))
        srv = QueryServer(engine=eng, config=ServiceConfig(capacity=32))
        srv.create_index("default", G.Points(jnp.asarray(pts)))
        r = srv.handle([within_request(q, 0.2)])[0]
        assert r.stats.route == force
        results[force] = r
    ref, got = results[ROUTE_LOOP], results[ROUTE_BRUTEFORCE]
    assert np.array_equal(got.counts, ref.counts)
    for ra, rb, c in zip(got.idxs, ref.idxs, ref.counts):
        assert set(ra[:c].tolist()) == set(rb[:c].tolist())


def test_server_survives_degenerate_index():
    """A cloud that shrinks to N < 2 must keep serving via the BVH's
    linear-scan fallback, not crash the exec paths."""
    srv = _server(300, seed=30)
    one = G.Points(jnp.asarray(_pts(1, 31)))
    srv.update_index("default", one)            # N change -> rebuild, tree=None
    q = _pts(3, 32)
    rs = srv.handle([knn_request(q, k=2), within_request(q, 10.0),
                     ray_request(q, np.ones((3, DIM), np.float32))])
    assert (rs[0].idxs[:, 0] == 0).all()        # the one point is everyone's NN
    assert (rs[0].idxs[:, 1] == -1).all()
    assert (rs[1].counts == 1).all()
    for r in rs:
        assert r.stats.route == ROUTE_LOOP and not r.stats.cache_hit


def test_exec_cache_keyed_on_indexable_getter():
    """Two same-shaped indexes with different getters must not share an
    executable (the jitted body closes over the getter)."""
    from repro.core.access import default_indexable_getter
    eng = QueryEngine(EngineConfig())
    srv = QueryServer(engine=eng, config=ServiceConfig(capacity=8))
    pts = _pts(100, 33)

    def fat_getter(values):     # inflate each point to a box
        b = default_indexable_getter(values)
        return G.Boxes(b.lo - 0.05, b.hi + 0.05)

    srv.create_index("plain", G.Points(jnp.asarray(pts)))
    srv.create_index("fat", G.Points(jnp.asarray(pts)), fat_getter)
    srv.handle([within_request(_pts(4, 34), 0.1, index="plain")])
    m1 = eng.stats.cache_misses
    srv.handle([within_request(_pts(4, 34), 0.1, index="fat")])
    assert eng.stats.cache_misses == m1 + 1     # distinct executable


def test_exec_cache_lru_eviction_bounded():
    """max_executables bounds the cache: the oldest executable is evicted
    and recompiles on return, so changing-N services can't grow forever."""
    eng = QueryEngine(EngineConfig(force="loop", max_executables=1))
    srv = QueryServer(engine=eng, config=ServiceConfig(capacity=8))
    srv.create_index("default", G.Points(jnp.asarray(_pts(300, 40))))
    srv.handle([within_request(_pts(5, 41), 0.1)])    # bucket 8 (cached)
    srv.handle([within_request(_pts(20, 42), 0.1)])   # bucket 32 evicts it
    assert len(eng._executables) == 1
    srv.handle([within_request(_pts(5, 41), 0.1)])    # bucket 8: re-miss
    assert eng.stats.cache_misses == 3 and eng.stats.cache_hits == 0


def test_warmup_defaults_warm_all_three_kinds_zero_cold_dispatch():
    """warmup(index) alone must cover the whole configured bucket ladder
    for ALL kinds — historically the ray route was silently skipped when
    no ray request appeared in the warmup mix."""
    srv = _server(300, seed=60, capacity=8,
                  config=ServiceConfig(capacity=8, min_bucket=8,
                                       max_bucket=32))
    srv.warmup("default")                      # no kinds, no bucket, no dim
    before = srv.engine.stats.snapshot()

    rng = np.random.default_rng(61)
    for m in (3, 9, 30):                       # buckets 8, 16, 32
        q = rng.uniform(0, 1, (m, DIM)).astype(np.float32)
        d = rng.normal(size=(m, DIM)).astype(np.float32)
        rs = srv.handle([knn_request(q, k=1), within_request(q, 0.1),
                         ray_request(q, d, k=1)])
        assert all(r.stats.cache_hit for r in rs)
    after = srv.engine.stats
    assert after.jit_traces == before.jit_traces      # zero cold dispatches
    assert after.cache_misses == before.cache_misses


def test_warmup_explicit_kinds_still_cover_missing_ones():
    """Passing only a knn mix must not leave ray/within cold (they warm at
    the default k)."""
    srv = _server(300, seed=62, capacity=8)
    srv.warmup("default", [("knn", 8)], max_bucket=8, dim=DIM)
    before = srv.engine.stats.snapshot()
    q = _pts(4, 63)
    rs = srv.handle([ray_request(q, np.ones((4, DIM), np.float32), k=1),
                     within_request(q, 0.1)])
    assert all(r.stats.cache_hit for r in rs)
    assert srv.engine.stats.jit_traces == before.jit_traces


def test_warmup_rounds_max_bucket_up_to_pow2():
    """max_bucket=100 must also warm the 128 bucket that 65..100-query
    requests ride in — no cold dispatch for any m <= max_bucket."""
    srv = _server(300, seed=50, capacity=8)
    srv.warmup("default", [("knn", 2)], max_bucket=100, dim=DIM)
    r = srv.handle([knn_request(_pts(100, 51), k=2)])[0]
    assert r.stats.bucket == 128 and r.stats.cache_hit

"""THE unified-API acceptance bar (ISSUE 5): one parametrized suite runs
the SAME query scenarios — spatial CSR, kNN, rays, callbacks with
attach_data payloads, empty/degenerate inputs — against BVH, BruteForce,
and DistributedTree through the one polymorphic ``Index.query()``.

DistributedTree runs on a single-shard mesh here (the collective paths
are identical code; the multi-shard semantics are pinned by
tests/test_distributed.py on 8 fake devices)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import AxisType, make_mesh
from repro.core import geometry as G, predicates as P, callbacks as CB
from repro.core.brute_force import BruteForce
from repro.core.bvh import BVH
from repro.core.distributed import DistributedTree
from repro.core.engine import EngineConfig, QueryEngine
from repro.core.index import Index, QueryResult

N, Q, DIM = 200, 16, 3
BACKENDS = ["bvh", "bruteforce", "distributed"]


def _pts(n, seed=0):
    r = np.random.default_rng(seed)
    return r.uniform(0, 1, (n, DIM)).astype(np.float32)


_PTS = _pts(N, seed=1)
_QP = _pts(Q, seed=2)
_D = np.linalg.norm(_QP[:, None] - _PTS[None], axis=-1)


def make_index(kind, coords=None) -> Index:
    values = G.Points(jnp.asarray(_PTS if coords is None else coords))
    if kind == "bvh":
        # Pinned to the loop route: at N * Q <= engine.BF_MAX_WORK an
        # unforced BVH would answer through BruteForce's own code.
        return BVH(values, engine=QueryEngine(EngineConfig(force="loop")))
    if kind == "bruteforce":
        return BruteForce(values)
    mesh = make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    return DistributedTree(mesh, "data", values)


@pytest.fixture(params=BACKENDS)
def index(request):
    return make_index(request.param)


def _sphere_preds(radius=0.3, q=None):
    qp = jnp.asarray(_QP if q is None else q)
    return P.intersects(G.Spheres(qp, jnp.full((len(qp),), radius,
                                               jnp.float32)))


# ---------------------------------------------------------------------------
# spatial CSR
# ---------------------------------------------------------------------------

def test_spatial_csr_matches_oracle(index):
    res = index.query(_sphere_preds())
    assert isinstance(res, QueryResult)
    off, idx = np.asarray(res.offsets), np.asarray(res.indices)
    want = _D <= 0.3
    assert np.array_equal(np.diff(off), want.sum(1))
    for i in range(Q):
        assert set(idx[off[i]:off[i + 1]].tolist()) \
            == set(np.where(want[i])[0].tolist())
    assert np.array_equal(np.asarray(index.count(_sphere_preds())),
                          want.sum(1))


def test_spatial_capacity_doubling_and_overflow(index):
    preds = _sphere_preds(10.0)            # every value matches every query
    res = index.query(preds, capacity=7)   # 7 -> doubled until 200 fits
    assert not res.overflow
    assert np.array_equal(np.diff(np.asarray(res.offsets)),
                          np.full(Q, N))
    res_t = index.query(preds, capacity=7,
                        policy=index.policy.override(max_doublings=0,
                                                     capacity=7))
    assert res_t.overflow
    assert np.array_equal(np.diff(np.asarray(res_t.offsets)), np.full(Q, 7))


# ---------------------------------------------------------------------------
# kNN
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [1, 5])
def test_knn_matches_oracle(index, k):
    res = index.query(P.nearest(G.Points(jnp.asarray(_QP)), k=k))
    want = np.sort(_D, axis=1)[:, :k]
    assert res.distances.shape == res.indices.shape == (Q, k)
    assert np.allclose(np.asarray(res.distances), want, atol=1e-5)
    # indices achieve the distances
    got = np.take_along_axis(_D, np.asarray(res.indices), axis=1)
    assert np.allclose(got, want, atol=1e-5)


def test_knn_k_exceeds_n_pads(index):
    res = index.query(P.nearest(G.Points(jnp.asarray(_QP)), k=N + 3))
    d, i = np.asarray(res.distances), np.asarray(res.indices)
    assert (i[:, N:] == -1).all() and np.isinf(d[:, N:]).all()
    assert np.allclose(np.sort(_D, 1), d[:, :N], atol=1e-5)


# ---------------------------------------------------------------------------
# rays
# ---------------------------------------------------------------------------

def _axis_rays(n=8, seed=5):
    """Axis-aligned rays through known points: the other two coordinates
    match EXACTLY, so the degenerate point-box slab test is fp-exact."""
    r = np.random.default_rng(seed)
    targets = r.integers(0, N, n)
    o = _PTS[targets].copy()
    o[:, 0] -= 1.0
    d = np.tile([1.0, 0.0, 0.0], (n, 1)).astype(np.float32)
    return G.Rays(jnp.asarray(o), jnp.asarray(d)), targets


def test_ray_nearest_matches_oracle(index):
    rays, targets = _axis_rays()
    res = index.query(P.RayNearest(rays, 1))
    t = np.asarray(res.distances)[:, 0]
    assert np.isfinite(t).all()
    assert np.all(t <= 1.0 + 1e-4)          # hit at/before the target point
    # the reported hit actually lies on each ray (x fired along +x)
    hit_idx = np.asarray(res.indices)[:, 0]
    o = np.asarray(rays.origin)
    assert np.allclose(_PTS[hit_idx][:, 1:], o[:, 1:], atol=1e-6)


# ---------------------------------------------------------------------------
# callbacks + attach_data (end-to-end payload delivery, ISSUE 5 satellite)
# ---------------------------------------------------------------------------

def test_callback_counting(index):
    got = index.query(_sphere_preds(), callback=CB.counting())
    assert np.array_equal(np.asarray(got), (_D <= 0.3).sum(1))


def test_attach_data_payload_reaches_callbacks(index):
    """The §2.2 contract on every backend: per-predicate payloads attached
    with ``attach_data`` arrive at the callback as ``pred.data`` — on
    DistributedTree the callback runs on the data-owning shard and the
    payload crosses with the gathered predicates."""
    payload = jnp.arange(Q, dtype=jnp.float32) * 10 + 1
    preds = P.attach_data(_sphere_preds(0.25), payload)

    def cb(state, pred, value, index_, t):
        return jnp.maximum(state, pred.data), jnp.bool_(False)

    pol = index.policy.override(combine=lambda a, b: jnp.maximum(a, b))
    got = index.query(preds, callback=(cb, jnp.float32(-1.0)), policy=pol)
    want = np.where((_D <= 0.25).any(1), np.asarray(payload), -1.0)
    assert np.allclose(np.asarray(got), want)


# ---------------------------------------------------------------------------
# empty / degenerate inputs
# ---------------------------------------------------------------------------

def test_empty_predicate_batch(index):
    preds = P.intersects(G.Spheres(jnp.zeros((0, DIM), jnp.float32),
                                   jnp.zeros((0,), jnp.float32)))
    res = index.query(preds)
    assert res.indices.shape == (0,)
    assert np.array_equal(np.asarray(res.offsets), np.zeros(1, np.int32))
    kres = index.query(P.nearest(G.Points(jnp.zeros((0, DIM), jnp.float32)),
                                 k=3))
    assert kres.indices.shape == (0, 3)


@pytest.mark.parametrize("kind", ["bvh", "bruteforce"])
def test_degenerate_value_counts(kind):
    """N in {0, 1}: single-process indexes fall back to a linear scan and
    keep every contract; DistributedTree documents its >= 2-per-shard
    floor with a loud error instead."""
    q = _sphere_preds(10.0)
    for n in (0, 1):
        idx = make_index(kind, coords=_pts(n, seed=9) if n else
                         np.zeros((0, DIM), np.float32))
        assert idx.size() == n and idx.empty() == (n == 0)
        assert np.all(np.asarray(idx.count(q)) == n)
        res = idx.query(q)
        assert np.array_equal(np.asarray(res.offsets), np.arange(Q + 1) * n)


def test_distributed_count_ignores_custom_combine_policy():
    """Counting must psum across shards even when the index's bound policy
    carries a custom callback-combine monoid (regression: override(None)
    silently kept the monoid)."""
    from repro.core.index import ExecutionPolicy
    mesh = make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    dt = DistributedTree(
        mesh, "data", G.Points(jnp.asarray(_PTS)),
        policy=ExecutionPolicy(combine=lambda a, b: jnp.minimum(a, b)))
    got = dt.count(_sphere_preds())
    assert np.array_equal(np.asarray(got), (_D <= 0.3).sum(1))
    # the CSR query sizes its capacity through the same counting path
    res = dt.query(_sphere_preds())
    assert np.array_equal(np.diff(np.asarray(res.offsets)),
                          (_D <= 0.3).sum(1))


def test_legacy_three_positional_constructor_still_shims():
    """API v1 allowed BVH(space, values, getter) positionally; the shim
    must warn, not TypeError."""
    from repro.core import index as IX
    from repro.core.access import default_indexable_getter
    vals = G.Points(jnp.asarray(_PTS))
    IX._SEEN_DEPRECATIONS.clear()
    with pytest.warns(DeprecationWarning):
        bvh = BVH(None, vals, default_indexable_getter)
    assert bvh.size() == N
    IX._SEEN_DEPRECATIONS.clear()
    with pytest.warns(DeprecationWarning):
        bf = BruteForce(None, vals, default_indexable_getter)
    assert bf.size() == N
    IX._SEEN_DEPRECATIONS.clear()
    with pytest.raises(TypeError, match="positional"):
        BVH(vals, default_indexable_getter, default_indexable_getter)


def test_distributed_degenerate_raises():
    mesh = make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    with pytest.raises(ValueError, match=">= 2 values per shard"):
        DistributedTree(mesh, "data",
                        G.Points(jnp.zeros((1, DIM), jnp.float32)))


# ---------------------------------------------------------------------------
# result identity across backends (the §2.1 "one interface" claim)
# ---------------------------------------------------------------------------

def test_all_backends_agree_pairwise():
    results = {b: make_index(b).query(_sphere_preds(0.2)) for b in BACKENDS}
    ref = results["bvh"]
    off = np.asarray(ref.offsets)
    for b in ("bruteforce", "distributed"):
        got = results[b]
        assert np.array_equal(np.asarray(got.offsets), off)
        gi, ri = np.asarray(got.indices), np.asarray(ref.indices)
        for i in range(Q):
            assert set(gi[off[i]:off[i + 1]].tolist()) \
                == set(ri[off[i]:off[i + 1]].tolist())


# ---------------------------------------------------------------------------
# value shipping (ISSUE 10 satellite): the policy-gated opt-in closes the
# _gather_values / QueryResult.values asymmetry for attach-data scenarios
# ---------------------------------------------------------------------------

def test_distributed_ship_values_matches_local_gather():
    dist = make_index("distributed")
    ref = make_index("bvh")
    ship = dist.policy.override(ship_values=True)

    # default stays None (the §2.3 contract) — opting in populates values
    # with exactly what a local backend gathers, for CSR and kNN alike
    assert dist.query(_sphere_preds(0.25)).values is None
    got = dist.query(_sphere_preds(0.25), policy=ship)
    want = ref.query(_sphere_preds(0.25))
    assert got.values is not None
    assert np.array_equal(np.asarray(got.offsets), np.asarray(want.offsets))
    assert np.allclose(np.asarray(got.values.coords),
                       _PTS[np.asarray(got.indices)])

    gk = dist.query(P.nearest(G.Points(jnp.asarray(_QP)), k=3), policy=ship)
    assert gk.values.coords.shape == (Q, 3, DIM)
    assert np.allclose(np.asarray(gk.values.coords),
                       _PTS[np.maximum(np.asarray(gk.indices), 0)])

    # empty batch: no collective, empty values pytree
    empty = dist.query(_sphere_preds(0.25, q=np.zeros((0, DIM), np.float32)),
                       policy=ship)
    assert empty.values.coords.shape == (0, DIM)


# ---------------------------------------------------------------------------
# the same scenarios served through an 8-device ShardedIndexStore (ISSUE 10):
# sharded serving must answer IDENTICALLY to the single-device QueryServer
# ---------------------------------------------------------------------------

def test_conformance_scenarios_served_sharded_8dev(subproc):
    subproc("""
import numpy as np, jax, jax.numpy as jnp
from repro.compat import make_mesh
from repro.core import geometry as G
from repro.service import (IndexStore, QueryServer, ServiceConfig,
                           ShardedIndexStore, knn_request, ray_request,
                           within_request)
assert jax.device_count() == 8
N, Q, DIM = 200, 16, 3
pts = np.random.default_rng(1).uniform(0, 1, (N, DIM)).astype(np.float32)
qp = np.random.default_rng(2).uniform(0, 1, (Q, DIM)).astype(np.float32)
D = np.linalg.norm(qp[:, None] - pts[None], axis=-1)

cfg = ServiceConfig(capacity=64, min_bucket=8, max_bucket=64)
sharded = QueryServer(store=ShardedIndexStore(make_mesh((8,), ("data",)),
                                              "data"), config=cfg)
sharded.create_index("default", pts)
plain = QueryServer(store=IndexStore(), config=cfg)
plain.create_index("default", G.Points(jnp.asarray(pts)))

# axis-aligned rays through known points (fp-exact slab tests)
targets = np.random.default_rng(5).integers(0, N, Q)
o = pts[targets].copy(); o[:, 0] -= 1.0
d = np.tile([1.0, 0.0, 0.0], (Q, 1)).astype(np.float32)

reqs = [knn_request(qp, 1), knn_request(qp, 5), within_request(qp, 0.3),
        ray_request(o, d, 1)]
got, want = sharded.handle(list(reqs)), plain.handle(list(reqs))

for k, r in ((1, got[0]), (5, got[1])):
    assert np.allclose(r.dists, np.sort(D, 1)[:, :k], atol=1e-5)
    assert np.allclose(np.take_along_axis(D, r.idxs, 1),
                       np.sort(D, 1)[:, :k], atol=1e-5)
    assert r.stats.route == "sharded"
for g, w in ((got[0], want[0]), (got[1], want[1])):
    assert np.allclose(g.dists, w.dists, atol=1e-6)
    assert np.array_equal(g.idxs, w.idxs)

assert np.array_equal(got[2].counts, (D <= 0.3).sum(1))
assert np.array_equal(got[2].counts, want[2].counts)
assert got[2].overflow == want[2].overflow == False
for i, (g, w) in enumerate(zip(got[2].idxs, want[2].idxs)):
    assert set(g[g >= 0].tolist()) == set(w[w >= 0].tolist()) \
        == set(np.where(D[i] <= 0.3)[0].tolist())

t = got[3].dists[:, 0]
assert np.isfinite(t).all() and np.all(t <= 1.0 + 1e-4)
hit = got[3].idxs[:, 0]
assert np.allclose(pts[hit][:, 1:], o[:, 1:], atol=1e-6)
assert np.allclose(got[3].dists, want[3].dists, atol=1e-6)
print("OK")
""")

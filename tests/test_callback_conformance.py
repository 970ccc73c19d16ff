"""Callback conformance: per-query final states of the while loop
(``traversal.traverse`` / ``BVH.query(callback=)``) against brute force's
callback path, which applies the same callback to every match in index
order — standard factories, early exit, pytree states, ray predicates and
callbacks that close over arrays (the dbscan pattern).

The loop visits matches in traversal order, so states that do not depend
on order are compared exactly, and collected indices as sets."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import callbacks as CB
from repro.core import geometry as G
from repro.core import predicates as P
from repro.core import traversal as T
from repro.core.brute_force import BruteForce
from repro.core.bvh import BVH
from repro.core.index import _bcast_state
from repro.core.lbvh import build


def _pts(n, dim=3, seed=0):
    r = np.random.default_rng(seed)
    return jnp.asarray(r.uniform(0, 1, (n, dim)).astype(np.float32))


def _run_both(tree, values, preds, cb, s0):
    """(loop final states, brute-force final states) as numpy leaves."""
    s0b = _bcast_state(s0, len(preds))
    loop = T.traverse(tree, values, preds, cb, s0b)
    bf = BruteForce(values)._query_callback_impl(preds, cb, s0b, None)
    loop, bf = (jax.tree_util.tree_leaves(x) for x in (loop, bf))
    for a, b in zip(loop, bf):
        assert a.dtype == b.dtype and a.shape == b.shape
    return [np.asarray(a) for a in loop], [np.asarray(b) for b in bf]


def _assert_exact(tree, values, preds, cb, s0):
    loop, bf = _run_both(tree, values, preds, cb, s0)
    for a, b in zip(loop, bf):
        assert np.array_equal(a, b)
    return loop


def _scene(n=300, q=37, r=0.25, seed=1):
    pts = _pts(n, 3, seed=seed)
    tree = build(G.Boxes(pts, pts))
    preds = P.intersects(G.Spheres(_pts(q, 3, seed=seed + 50),
                                   jnp.full((q,), r, jnp.float32)))
    return tree, G.Points(pts), preds


@pytest.mark.parametrize("factory", [
    CB.counting,
    lambda: CB.count_with_limit(3),          # early exit retires lanes
    CB.min_distance,
], ids=["counting", "count_with_limit", "min_distance"])
def test_order_free_callbacks_match_bruteforce(factory):
    tree, values, preds = _scene()
    cb, s0 = factory()
    _assert_exact(tree, values, preds, cb, s0)


@pytest.mark.parametrize("factory,cap", [
    (lambda: CB.collect_first_k(5), 5),      # early exit at k
    (lambda: CB.collect_hits(16), 16),       # full count, (cap,) rows
], ids=["collect_first_k", "collect_hits"])
def test_collecting_callbacks_match_bruteforce_as_sets(factory, cap):
    """Counts equal; the collected indices are the match set where it fits
    the buffer, and a same-sized part of it where it does not."""
    tree, values, preds = _scene()
    cb, s0 = factory()
    (cnt, idx, ts), (cnt_b, idx_b, ts_b) = _run_both(tree, values, preds,
                                                     cb, s0)
    assert np.array_equal(cnt, cnt_b)
    full = BruteForce(values).query(preds)
    off, hits = np.asarray(full.offsets), np.asarray(full.indices)
    truncated = 0
    for i in range(len(preds)):
        want = set(hits[off[i]:off[i + 1]].tolist())
        got, got_b = (set(x[i][x[i] >= 0].tolist()) for x in (idx, idx_b))
        if len(want) <= cap:
            assert got == got_b == want
        else:
            truncated += 1
            assert len(got) == len(got_b) == cap
            assert got <= want and got_b <= want
    assert 0 < truncated < len(preds)        # both regimes exercised
    # spatial hits carry t = 0: the same multiset of ts in every row
    assert np.array_equal(np.sort(ts, -1), np.sort(ts_b, -1))


def test_sum_payload_and_attached_data():
    """A float sum over the matches. The loop and brute force each sum
    every match once (their match counts are exact), and each row lies
    within the float32 rounding bound of a float64 sum over the oracle's
    match set: m rounded terms and m - 1 additions of non-negative terms
    err by at most gamma_m = m*u / (1 - m*u) times the exact sum, so a
    lost or doubled match larger than that bound cannot pass."""
    tree, values, preds = _scene()
    payload = np.arange(len(preds), dtype=np.float32)
    preds = P.attach_data(preds, jnp.asarray(payload))
    cb = CB.sum_payload(lambda pred, value: pred.data + value.coords[0])
    (loop,), (bf,) = _run_both(tree, values, preds, cb, jnp.float32(0))
    (cnt,), (cnt_b,) = _run_both(tree, values, preds, *CB.counting())
    full = BruteForce(values).query(preds)
    off, hits = np.asarray(full.offsets), np.asarray(full.indices)
    m = np.diff(off)
    assert np.array_equal(cnt, m) and np.array_equal(cnt_b, m)
    x = np.asarray(values.coords)[:, 0].astype(np.float64)
    exact = np.array([np.sum(payload[i] + x[hits[off[i]:off[i + 1]]])
                      for i in range(len(preds))])
    u = np.finfo(np.float32).eps / 2
    bound = m * u / (1 - m * u) * exact
    assert np.any(loop > 0)
    assert np.all(np.abs(loop - exact) <= bound)
    assert np.all(np.abs(bf - exact) <= bound)


def test_closure_capturing_callback():
    """Callbacks closing over int/bool arrays (dbscan's is_core/labels)."""
    tree, values, preds = _scene(n=200, q=29)
    flags = jnp.asarray(np.random.default_rng(3).random(200) < 0.5)
    weights = jnp.arange(200, dtype=jnp.int32)
    big = jnp.int32(10**6)

    def cb(state, pred, value, index, t):
        w = jnp.where(flags[index], weights[index], big)
        return jnp.minimum(state, w), jnp.bool_(False)

    _assert_exact(tree, values, preds, cb, big)


def test_bool_state_leaf_keeps_its_dtype():
    tree, values, preds = _scene(n=150, q=17)

    def cb(state, pred, value, index, t):
        return (state[0] | (index % 2 == 0), state[1] + 1), jnp.bool_(False)

    got = _assert_exact(tree, values, preds, cb,
                        (jnp.bool_(False), jnp.int32(0)))
    assert got[0].dtype == np.bool_


@pytest.mark.parametrize("kind", ["intersect", "ordered", "nearest"])
def test_ray_predicates_match_bruteforce(kind):
    r0 = np.random.default_rng(5)
    pts = _pts(256, 3, seed=6)
    tree = build(G.Boxes(pts, pts + 0.05))
    values = G.Boxes(pts, pts + 0.05)
    o = jnp.asarray(r0.uniform(0, 1, (21, 3)).astype(np.float32))
    d = jnp.asarray(r0.normal(size=(21, 3)).astype(np.float32))
    rays = G.Rays(o, d)
    preds = {"intersect": P.RayIntersect(rays),
             "ordered": P.RayOrderedIntersect(rays),
             "nearest": P.RayNearest(rays, 1)}[kind]
    cb, s0 = CB.min_distance()
    (t_min,) = _assert_exact(tree, values, preds, cb, s0)
    assert np.isfinite(t_min).any()


def test_query_callback_end_to_end_matches_bruteforce():
    """Index.query(callback=) on a BVH agrees with the same call on a
    BruteForce index over the same values."""
    pts = _pts(600, 3, seed=12)
    vals = G.Points(pts)
    preds = P.intersects(G.Spheres(_pts(64, 3, seed=13),
                                   jnp.full((64,), 0.2, jnp.float32)))
    cb, s0 = CB.counting()
    a = np.asarray(BVH(vals).query(preds, callback=(cb, s0)))
    b = np.asarray(BruteForce(vals).query(preds, callback=(cb, s0)))
    assert a.sum() > 0
    assert np.array_equal(a, b)

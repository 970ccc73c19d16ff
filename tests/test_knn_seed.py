"""The kNN walk's seeded pruning bound (DESIGN.md §2 item 8).

Before its rope-order walk, a spatial kNN query descends one path of the
tree into the nearer child, reads the sorted leaves around the leaf it
reaches, and prunes from the start with the k-th smallest of their
distances. The answers must stay the exact k nearest: each case here
compares ``traverse_knn`` with a float64 brute force, distances by rank
and indices wherever the distances are not tied.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import geometry as G
from repro.core import lbvh
from repro.core import predicates as P
from repro.core import traversal as T

DIM = 3


def _rng(seed):
    return np.random.default_rng(seed)


def _uniform(n, q, seed):
    r = _rng(seed)
    return r.uniform(0, 1, (n, DIM)), r.uniform(0, 1, (q, DIM))


def _clusters(n, q, seed):
    """Two tight clusters, queries far from both."""
    r = _rng(seed)
    pts = np.concatenate([r.normal(0.1, 1e-3, (n // 2, DIM)),
                          r.normal(0.9, 1e-3, (n - n // 2, DIM))])
    return pts, r.uniform(0.4, 0.6, (q, DIM))


def _duplicates(n, q, seed):
    """Every point one of 40 sites, so the k-th rank is tied; half the
    queries sit on a site."""
    r = _rng(seed)
    sites = r.uniform(0, 1, (40, DIM))
    pts = sites[r.integers(0, 40, n)]
    return pts, np.concatenate([sites[:q // 2], r.uniform(0, 1, (q - q // 2,
                                                                DIM))])


CLOUDS = {"uniform": _uniform, "clusters": _clusters,
          "duplicates": _duplicates}


def _tree(pts):
    p = jnp.asarray(pts, jnp.float32)
    return lbvh.build(G.Boxes(p, p)), G.Points(p)


def _query(geom, qs):
    q = jnp.asarray(qs, jnp.float32)
    if geom == "points":
        return G.Points(q)
    if geom == "boxes":
        return G.Boxes(q - 0.01, q + 0.01)
    return G.Spheres(q, jnp.full((len(qs),), 0.02, jnp.float32))


def _brute(pts, qs, k, allowed=None):
    """float64 distances from each query (the centre of a box or sphere,
    as ``leaf_distance`` measures) to every point, sorted: (dists, order)
    of the first k, inf where fewer than k points are allowed."""
    pts = np.asarray(pts, np.float32).astype(np.float64)
    qs = np.asarray(qs, np.float32).astype(np.float64)
    d = np.sqrt(((qs[:, None, :] - pts[None, :, :]) ** 2).sum(-1))
    if allowed is not None:
        d = np.where(allowed, d, np.inf)
    order = np.argsort(d, axis=1, kind="stable")
    d = np.take_along_axis(d, order, 1)
    pad = max(0, k - d.shape[1])
    return (np.pad(d, ((0, 0), (0, pad)), constant_values=np.inf)[:, :k],
            np.pad(order, ((0, 0), (0, pad)), constant_values=-1)[:, :k])


def _assert_exact(dists, idxs, pts, qs, k, allowed=None):
    dists, idxs = np.asarray(dists), np.asarray(idxs)
    ref_d, ref_i = _brute(pts, qs, k, allowed)
    finite = np.isfinite(ref_d)
    assert np.array_equal(np.isfinite(dists), finite)
    assert np.array_equal(idxs < 0, ~finite)
    np.testing.assert_allclose(dists[finite], ref_d[finite], rtol=0,
                               atol=1e-6)
    # an index whose reference distance is tied with no neighbour of any
    # rank is the reference's own; tied ones are checked by distance
    full, _ = _brute(pts, qs, max(len(pts), k), allowed)
    lo = np.concatenate([np.full((len(qs), 1), -np.inf), full[:, :-1]], 1)
    hi = np.concatenate([full[:, 1:], np.full((len(qs), 1), np.inf)], 1)
    with np.errstate(invalid="ignore"):     # inf - inf past the last point
        untied = ((full - lo > 1e-6) & (hi - full > 1e-6))[:, :k] & finite
    assert np.array_equal(idxs[untied], ref_i[untied])
    for row in range(len(qs)):      # each served index at its distance
        got = idxs[row][idxs[row] >= 0]
        assert len(set(got.tolist())) == len(got)
        if allowed is not None:
            assert allowed[row, got].all()
    own = np.sqrt(((np.asarray(qs, np.float32).astype(np.float64)[:, None]
                    - np.asarray(pts, np.float32).astype(np.float64)[
                        np.maximum(idxs, 0)]) ** 2).sum(-1))
    np.testing.assert_allclose(own[finite], dists[finite], rtol=0, atol=1e-6)


@pytest.mark.parametrize("k", [1, 10, 32])
@pytest.mark.parametrize("geom", ["points", "boxes", "spheres"])
@pytest.mark.parametrize("cloud", sorted(CLOUDS))
def test_seeded_walk_is_exact(cloud, geom, k):
    pts, qs = CLOUDS[cloud](3000, 120, 11)
    tree, vals = _tree(pts)
    preds = P.nearest(_query(geom, qs), k=k)
    d, i, _, seeded = T.traverse_knn(tree, vals, preds, k, count_steps=True)
    _assert_exact(d, i, pts, np.asarray(G.centroid(preds.geom)), k)
    assert np.asarray(seeded).all()
    # counting the seed's reads leaves the answers bit-identical
    d2, i2 = T.traverse_knn(tree, vals, preds, k)
    assert np.array_equal(np.asarray(d2).view(np.int32),
                          np.asarray(d).view(np.int32))
    assert np.array_equal(np.asarray(i2), np.asarray(i))


@pytest.mark.parametrize("n,k", [(5, 10), (9, 10), (20, 10), (40, 32)])
def test_small_trees_keep_their_padding(n, k):
    """n < k pads with (inf, -1) and seeds nothing; n < the seed window
    reads every leaf."""
    pts, qs = _uniform(n, 16, n)
    tree, vals = _tree(pts)
    assert T.seed_window(k, n) == min(n, T.seed_window(k, 1 << 30))
    d, i, _, seeded = T.traverse_knn(
        tree, vals, P.nearest(G.Points(jnp.asarray(qs, jnp.float32)), k=k),
        k, count_steps=True)
    _assert_exact(d, i, pts, qs, k)
    assert (np.asarray(seeded) == (n >= k)).all()


@pytest.mark.parametrize("k,window", [(1, 4), (3, 16), (10, 32), (11, 64),
                                      (32, 128)])
def test_seed_window_is_the_power_of_two_at_least_three_k(k, window):
    assert T.seed_window(k, 1 << 20) == window
    assert T.seed_window(k, 7) == min(7, window)


@pytest.mark.parametrize("k", [1, 10])
@pytest.mark.parametrize("block", [64, 512])
def test_exclusion_labels_rule_out_seeds(k, block):
    """EMST's query: nearest outside the query's component. Components are
    runs of `block` sorted leaves, so most of the seed window around a
    point lies in its own component and counts as inf."""
    pts, _ = _uniform(2048, 1, 5)
    tree, vals = _tree(pts)
    perm = np.asarray(tree.leaf_perm)
    labels = np.empty(len(pts), np.int32)
    labels[perm] = np.arange(len(pts)) // block
    qs = pts[::16]
    q_labels = labels[::16]
    d, i, _, seeded = T.traverse_knn(
        tree, vals, P.nearest(G.Points(jnp.asarray(qs, jnp.float32)), k=k),
        k, exclude_labels=jnp.asarray(q_labels),
        leaf_labels=jnp.asarray(labels), count_steps=True)
    allowed = labels[None, :] != q_labels[:, None]
    _assert_exact(d, i, pts, qs, k, allowed)
    seeded = np.asarray(seeded)
    assert not seeded.all()     # windows wholly inside a component
    if block == 64:
        assert seeded.any()


def test_ray_nearest_walk_is_unseeded_and_exact():
    """First-k ray hits keep the unbounded walk: no seed, and the hits are
    the brute force's by the ray parameter."""
    r = _rng(9)
    c = r.uniform(0, 1, (1500, DIM)).astype(np.float32)
    boxes = G.Boxes(jnp.asarray(c - 0.01), jnp.asarray(c + 0.01))
    tree = lbvh.build(boxes)
    o = r.uniform(-0.5, 0, (64, DIM)).astype(np.float32)
    dirs = r.normal(size=(64, DIM)).astype(np.float32)
    dirs[:, 0] = np.abs(dirs[:, 0]) + 0.5
    rays = G.Rays(jnp.asarray(o), jnp.asarray(dirs))
    k = 4
    t, i, _, seeded = T.traverse_knn(tree, boxes, P.RayNearest(rays, k), k,
                                     count_steps=True)
    assert not np.asarray(seeded).any()
    hit, t_all = jax.vmap(lambda oo, dd: G.ray_box(
        oo[None], dd[None], boxes.lo, boxes.hi))(rays.origin, rays.direction)
    t_all = np.where(np.asarray(hit), np.asarray(t_all), np.inf)
    ref = np.sort(t_all, axis=1)[:, :k]
    np.testing.assert_allclose(np.asarray(t), ref, rtol=1e-6)
    assert np.array_equal(np.asarray(i) < 0, ~np.isfinite(ref))


def test_seeded_walk_reads_few_nodes():
    """The seed's purpose, pinned: on 2^16 uniform points with k = 10 the
    mean nodes read per query, the descent's included, stays below 400
    (the walk that starts unbounded reads ~2,700 here)."""
    pts, qs = _uniform(1 << 16, 512, 7)
    tree, vals = _tree(pts)
    _, _, steps, seeded = T.traverse_knn(
        tree, vals, P.nearest(G.Points(jnp.asarray(qs, jnp.float32)), k=10),
        10, count_steps=True)
    assert np.asarray(seeded).all()
    assert np.asarray(steps).mean() < 400


def _unseeded(monkeypatch, tree, vals, preds, k):
    """The same walk started from an unbounded `tau`, as before the seed."""
    big = jnp.asarray(jnp.inf, tree.node_lo.dtype)
    monkeypatch.setattr(T, "_seed_bound",
                        lambda *_: (big, jnp.int32(0)))
    return T.traverse_knn.__wrapped__(tree, vals, preds, k)


def _near_surfaces(kind, seed, q=200):
    """Values at coordinates ~100, where one float32 ulp is ~8e-6, and
    queries 1e-6 to 1e-3 off a sphere along an axis or past a segment's or
    triangle's end point ``b``: the k-th distance is then small enough for
    the rounding gap between a leaf box's distance and the value's own to
    exceed the bound's relative widening. For spheres the gap is ``c + r``
    rounded; they come in groups of 12 identical ones, so that the k-th
    neighbour is that close at k = 10 too. Segments and triangles lie apart
    on a grid of lines along x, from ``a`` at x < -30 to ``b`` at x ~100,
    so that ``b - a`` lands in a coarser binade than ``b`` and
    ``a + (b - a)`` rounds off ``b``; ``b`` is the corner of the value's
    box nearest the query."""
    r = _rng(seed)
    f32 = lambda x: np.asarray(x, np.float32).astype(np.float64)
    gap = 10.0 ** r.uniform(-6, -3, (q, 1))
    if kind == "spheres":
        c = np.repeat(f32(100 + r.uniform(0, 1, (125, DIM))), 12, 0)
        rad = np.repeat(f32(r.uniform(1e-3, 1e-2, 125)), 12)
        vals = G.Spheres(jnp.asarray(c, jnp.float32),
                         jnp.asarray(rad, jnp.float32))
        pick = r.integers(0, len(c), q)
        axis = np.eye(DIM)[r.integers(0, DIM, q)]
        axis *= r.choice([-1.0, 1.0], (q, 1))
        qs = c[pick] + axis * (rad[pick, None] + gap)
    else:
        yz = np.stack(np.meshgrid(np.arange(39.0), np.arange(39.0)), -1)
        yz = 100 + yz.reshape(-1, 2) + r.uniform(0, 0.2, (39 * 39, 2))
        b = f32(np.concatenate([100 + r.uniform(0, 1, (len(yz), 1)), yz], 1))
        a = b.copy()
        a[:, 0] = f32(r.uniform(-60, -30, len(b)))
        if kind == "segments":
            vals = G.Segments(jnp.asarray(a, jnp.float32),
                              jnp.asarray(b, jnp.float32))
        else:
            e = f32(a - [0, 0.1, 0.1])
            vals = G.Triangles(jnp.asarray(a, jnp.float32),
                               jnp.asarray(b, jnp.float32),
                               jnp.asarray(e, jnp.float32))
        out = np.abs(r.normal(size=(q, DIM)))
        out /= np.linalg.norm(out, axis=1, keepdims=True)
        qs = b[r.integers(0, len(b), q)] + out * gap
    return lbvh.build(G.to_boxes(vals)), vals, qs


@pytest.mark.parametrize("k", [1, 10])
@pytest.mark.parametrize("kind", ["spheres", "segments", "triangles"])
def test_seeded_walk_is_exact_next_to_surfaces(monkeypatch, kind, k):
    """Where the k-th neighbour lies within a few ulps of the query, the
    seed's bound still admits its leaf: the answers are bit-identical to
    the unbounded walk's, and every row was seeded."""
    tree, vals, qs = _near_surfaces(kind, 21 + k)
    preds = P.nearest(G.Points(jnp.asarray(qs, jnp.float32)), k=k)
    d, i, _, seeded = T.traverse_knn(tree, vals, preds, k, count_steps=True)
    assert np.asarray(seeded).all()
    d0, i0 = _unseeded(monkeypatch, tree, vals, preds, k)
    assert np.array_equal(np.asarray(d).view(np.int32),
                          np.asarray(d0).view(np.int32))
    assert np.array_equal(np.asarray(i), np.asarray(i0))

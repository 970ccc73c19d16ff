"""The packed node table the traversal loop reads (DESIGN.md §2).

Each node's record (lo and hi as float bits, rope, range_last, child) is
one row gather away. These tests pin that the table unpacks bit for bit to
the tree's own arrays at every record width, and that every version a
build, a refit or a sharded update publishes carries a table current with
its boxes: queries answered through it match brute force.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import callbacks as CB
from repro.core import geometry as G
from repro.core import lbvh
from repro.core import predicates as P
from repro.core import traversal as T

# dim -> record lanes L (smallest power of two >= 2*dim + 3)
LANES = {1: 8, 2: 8, 3: 16, 7: 32, 64: 256}


def _pts(n, dim, seed):
    return np.random.default_rng(seed).uniform(
        0, 1, (n, dim)).astype(np.float32)


def _synthetic_tree(n, dim, seed, dtype=np.float32):
    """Node arrays of no real tree, boxes salted with +-inf and -0.0, and
    the table packed from them."""
    r = np.random.default_rng(seed)
    m = 2 * n - 1
    lo = r.normal(size=(m, dim)).astype(dtype)
    hi = r.normal(size=(m, dim)).astype(dtype)
    lo.flat[::7], hi.flat[::5] = -np.inf, np.inf
    lo.flat[3::11], hi.flat[1::13] = -0.0, -0.0
    hi.flat[4::17] = -np.inf
    rope = r.integers(-1, m, m).astype(np.int32)
    rlast = r.integers(0, n, m).astype(np.int32)
    left = r.integers(1, m, n - 1).astype(np.int32)
    perm = r.permutation(n).astype(np.int32)
    a = [jnp.asarray(x) for x in (lo, hi, rope, rlast, left, perm)]
    table = lbvh._pack_nodes(*a)
    return lbvh.LBVH(a[0], a[1], a[4], a[4], a[2], a[3], a[5], a[4], table)


def _read_all(tree):
    m = tree.node_lo.shape[0]
    return jax.vmap(lambda i: lbvh.node_record(tree, i))(
        jnp.arange(m, dtype=jnp.int32))


def _assert_unpacks_to_fields(tree):
    lo, hi, rope, rlast, child = map(np.asarray, _read_all(tree))
    bits = np.dtype(f"uint{8 * lo.dtype.itemsize}")
    assert lo.dtype == hi.dtype == tree.node_lo.dtype
    assert np.array_equal(lo.view(bits), np.asarray(tree.node_lo).view(bits))
    assert np.array_equal(hi.view(bits), np.asarray(tree.node_hi).view(bits))
    assert np.array_equal(rope, np.asarray(tree.rope))
    assert np.array_equal(rlast, np.asarray(tree.range_last))
    assert np.array_equal(child, np.concatenate(
        [np.asarray(tree.left_child), np.asarray(tree.leaf_perm)]))


@pytest.mark.parametrize("dim", sorted(LANES))
def test_node_table_unpacks_bit_for_bit(dim):
    n = 37                                   # 73 nodes: no width divides it
    tree = _synthetic_tree(n, dim, seed=dim)
    lanes = lbvh.node_record_lanes(dim)
    per_row = max(1, 128 // lanes)
    assert lanes == LANES[dim]
    assert tree.node_table.dtype == jnp.int32
    assert tree.node_table.shape == (-(-(2 * n - 1) // per_row),
                                     max(128, lanes))
    _assert_unpacks_to_fields(tree)


def test_node_table_keeps_half_precision_boxes_bit_for_bit():
    _assert_unpacks_to_fields(_synthetic_tree(37, 3, 0, np.float16))
    pts = _pts(200, 3, 1).astype(np.float16)
    _assert_unpacks_to_fields(
        lbvh.build(G.to_boxes(G.Points(jnp.asarray(pts)))))


@pytest.mark.parametrize("dim", [1, 2, 3, 7])
def test_built_tree_table_unpacks_to_its_arrays(dim):
    tree = lbvh.build(G.to_boxes(G.Points(jnp.asarray(_pts(301, dim, dim)))))
    _assert_unpacks_to_fields(tree)


# ---------------------------------------------------------------------------
# answers through the table vs brute force
# ---------------------------------------------------------------------------

def _within(tree, pts, q, radius):
    cb, s0 = CB.collect_hits(pts.shape[0])
    s0 = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a, (q.shape[0],) + jnp.shape(a)), s0)
    preds = P.intersects(G.Spheres(jnp.asarray(q),
                                   jnp.full((q.shape[0],), radius)))
    count, idxs, _ = T.traverse(tree, G.Points(jnp.asarray(pts)), preds,
                                cb, s0)
    return np.asarray(count), np.asarray(idxs)


def _assert_within_matches(tree, pts, q, radius):
    count, idxs = _within(tree, pts, q, radius)
    d2 = ((q[:, None, :].astype(np.float64) - pts[None]) ** 2).sum(-1)
    want = d2 <= radius * radius
    assert np.array_equal(count, want.sum(1))
    for row, w in zip(idxs, want):
        assert set(row[row >= 0].tolist()) == set(np.flatnonzero(w).tolist())


def _assert_knn_matches(tree, pts, q, k):
    d, i = T.traverse_knn(tree, G.Points(jnp.asarray(pts)),
                          P.nearest(G.Points(jnp.asarray(q)), k=k), k)
    d2 = ((q[:, None, :].astype(np.float64) - pts[None]) ** 2).sum(-1)
    want = np.sort(np.sqrt(d2), axis=1)[:, :k]
    assert np.allclose(np.asarray(d), want, atol=1e-5)
    got_i = np.asarray(i)
    assert np.allclose(np.sqrt(np.take_along_axis(d2, got_i, 1)), want,
                       atol=1e-5)


@pytest.mark.parametrize("dim", [1, 2, 3, 7])
@pytest.mark.parametrize("kind", ["within", "knn"])
def test_loop_answers_match_brute_force(kind, dim):
    pts = _pts(400, dim, seed=10 + dim)
    q = _pts(23, dim, seed=20 + dim)
    tree = lbvh.build(G.to_boxes(G.Points(jnp.asarray(pts))))
    if kind == "within":
        _assert_within_matches(tree, pts, q, 0.15 * dim ** 0.5)
    else:
        _assert_knn_matches(tree, pts, q, 5)


def test_ray_nearest_matches_brute_force():
    r = np.random.default_rng(5)
    lo = r.uniform(0, 1, (300, 3)).astype(np.float32)
    hi = lo + r.uniform(0.01, 0.08, (300, 3)).astype(np.float32)
    o = r.uniform(0, 1, (31, 3)).astype(np.float32)
    dirs = r.normal(size=(31, 3)).astype(np.float32)
    boxes = G.Boxes(jnp.asarray(lo), jnp.asarray(hi))
    tree = lbvh.build(boxes)
    t, i = T.traverse_knn(tree, boxes, P.RayNearest(
        G.Rays(jnp.asarray(o), jnp.asarray(dirs)), 1), 1)
    hit, tt = map(np.asarray, G.ray_box(o[:, None], dirs[:, None],
                                        lo[None], hi[None]))
    want_t = np.where(hit, tt, np.inf).min(1)
    assert hit.any(1).sum() > 5                  # some rays do hit
    assert np.array_equal(np.asarray(t)[:, 0], want_t)
    got = np.asarray(i)[:, 0]
    assert np.array_equal(got >= 0, hit.any(1))
    assert np.array_equal(tt[np.arange(len(o)), got][got >= 0],
                          want_t[got >= 0])


@pytest.mark.parametrize("step", ["refit", "refit_with_quality"])
def test_refit_republishes_the_table(step):
    """Points mirrored through the cube's centre move every box while the
    topology stays: a table left at the build's boxes would prune the
    wrong subtrees."""
    pts = _pts(500, 3, seed=3)
    moved = (1.0 - pts).astype(np.float32)
    tree = lbvh.build(G.to_boxes(G.Points(jnp.asarray(pts))))
    boxes = G.to_boxes(G.Points(jnp.asarray(moved)))
    if step == "refit":
        new = lbvh.refit(tree, boxes)
    else:
        new, _ = lbvh.refit_with_quality(tree, boxes)
    assert not np.array_equal(np.asarray(new.node_table),
                              np.asarray(tree.node_table))
    _assert_unpacks_to_fields(new)
    q = _pts(29, 3, seed=4)
    _assert_within_matches(new, moved, q, 0.12)
    _assert_knn_matches(new, moved, q, 6)


def test_sharded_update_republishes_every_shards_table_4dev(subproc):
    subproc("""
import numpy as np, jax, jax.numpy as jnp
from repro.compat import make_mesh
from repro.core import geometry as G
from repro.service import (QueryServer, ServiceConfig, ShardedIndexStore,
                           knn_request, within_request)
assert jax.device_count() == 4
rng = np.random.default_rng(7)
pts = rng.uniform(0, 1, (4 * 96, 3)).astype(np.float32)
moved = (1.0 - pts).astype(np.float32)
cfg = ServiceConfig(capacity=64, min_bucket=8, max_bucket=32)
server = QueryServer(store=ShardedIndexStore(make_mesh((4,), ("data",)),
                                             "data"), config=cfg)
server.create_index("default", pts)
e = server.update_index("default", G.Points(jnp.asarray(moved)))
assert e.action == "refit", e.action
q = rng.uniform(0, 1, (21, 3)).astype(np.float32)
got_knn, got_in = server.handle([knn_request(q, 5), within_request(q, 0.15)])
assert got_knn.stats.index_version == got_in.stats.index_version == 2
d2 = ((q[:, None, :].astype(np.float64) - moved[None]) ** 2).sum(-1)
want_d = np.sort(np.sqrt(d2), axis=1)[:, :5]
assert np.allclose(got_knn.dists, want_d, atol=1e-5)
assert np.allclose(np.sqrt(np.take_along_axis(d2, got_knn.idxs, 1)), want_d,
                   atol=1e-5)
want_in = d2 <= 0.15 * 0.15
assert np.array_equal(got_in.counts, want_in.sum(1))
for row, w in zip(got_in.idxs, want_in):
    assert set(row[row >= 0].tolist()) == set(np.flatnonzero(w).tolist())
print("OK")
""", devices=4)

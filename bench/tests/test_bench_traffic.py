"""The generator is a function of the seed, and every seed gets the same
amount of work."""
import numpy as np

from bench.tests.conftest import cell_of
from bench.traffic import Traffic

SEED = 2**31 + 12345          # the driver's seeds pass 32 signed bits


def _open():
    return cell_of("box-4M.within-open")


def test_same_seed_same_traffic():
    cell = _open()
    cfg = dict(cell.config, points=1000)
    a, b = Traffic(cell.traffic, cfg, SEED), Traffic(cell.traffic, cfg, SEED)
    assert a.schedule(10.0) == b.schedule(10.0)
    np.testing.assert_array_equal(a.points(), b.points())
    np.testing.assert_array_equal(a.queries(a.rng(3, 0), 5),
                                  b.queries(b.rng(3, 0), 5))
    for x, y in zip(a.moves(a.points(), 2), b.moves(b.points(), 2)):
        np.testing.assert_array_equal(x, y)


def test_seeds_share_the_work_in_another_order():
    cell = _open()
    cfg = dict(cell.config, points=1000)
    a = Traffic(cell.traffic, cfg, 1).schedule(10.0)
    b = Traffic(cell.traffic, cfg, 2).schedule(10.0)
    assert sorted(d.rows for d in a) == sorted(d.rows for d in b)
    assert [d.rows for d in a] != [d.rows for d in b]
    assert len(a) == len(b) == round(cell.traffic["rate_rps"] * 10.0)
    assert 0.0 == a[0].t and a[-1].t < 10.0
    # one set of gaps between arrivals, in another order: each seed's
    # schedule leaves out one gap of the set (the one before its first)
    ga = np.round(np.diff([d.t for d in a]), 9)
    gb = np.round(np.diff([d.t for d in b]), 9)
    assert len(np.intersect1d(ga, gb)) >= len(ga) - 2
    assert not np.array_equal(ga, gb)
    sizes = [d.rows for d in a]
    assert min(sizes) >= 1 and max(sizes) <= 256
    # log-uniform: about as many requests of 1-16 rows as of 16-256
    small = sum(s < 16 for s in sizes)
    assert 0.4 < small / len(sizes) < 0.6


def test_points_and_moves_stay_near_the_cube():
    cell = _open()
    t = Traffic(cell.traffic, dict(cell.config, points=4096), SEED)
    pts = t.points()
    assert pts.dtype == np.float32 and pts.shape == (4096, 3)
    assert pts.min() >= 0.0 and pts.max() < 1.0
    step = t.moves(pts, 1)[0] - pts
    spacing = 4096 ** (-1 / 3)
    assert abs(step.std() / spacing - 0.1) < 0.01


def test_update_times_and_buckets():
    cell = _open()
    t = Traffic(cell.traffic, cell.config, 0)
    assert t.update_times(30.0) == [2.5, 7.5, 12.5, 17.5, 22.5, 27.5]
    assert t.buckets(8, 1024) == [8, 16, 32, 64, 128, 256, 512, 1024]
    bulk = cell_of("box-4M.knn-bulk")
    assert Traffic(bulk.traffic, bulk.config, 0).buckets(8, 1024) == [1024]


def test_ranks_fill_their_own_boxes_in_rank_order():
    cell = cell_of("box-16M-4chip.within-bulk")
    cfg = dict(cell.config, points=4096)
    t = Traffic(cell.traffic, cfg, SEED)
    pts = t.points()
    assert pts.dtype == np.float32 and pts.shape == (4096, 3)
    corners = np.array([[0, 0, 0], [1, 0, 0], [0, 1, 0], [1, 1, 0]])
    for r, block in enumerate(np.split(pts, 4)):   # input-order shards
        assert (block >= corners[r]).all() and (block < corners[r] + 1).all()
    q = t.queries(t.rng(3, 0), 64)
    assert q.dtype == np.float32
    for r, block in enumerate(np.split(q, 4)):     # 16 rows from each rank
        own = np.split(pts, 4)[r]
        assert all((own == row).all(1).any() for row in block)
    # one rank's density: the radius holds 10 of its 1024 points
    assert t.radius == Traffic(cell.traffic, dict(cfg, points=1024, ranks=1),
                               SEED).radius

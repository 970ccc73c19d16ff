"""The copied reference against a full scan, and the numbers it compares
by: nought for exact answers, large for the bfloat16 control's."""
import numpy as np
import pytest

from bench.data import radius_for, uniform_points
from bench.reference import Reference


@pytest.fixture(scope="module")
def cloud():
    rng = np.random.default_rng(7)
    pts = uniform_points(rng, 3000)
    return pts, Reference(pts), rng.random((40, 3), dtype=np.float32)


def _scan(pts, c):
    return np.linalg.norm(pts.astype(np.float64) - c.astype(np.float64),
                          axis=1)


def test_grid_answers_match_a_full_scan(cloud):
    pts, ref, qs = cloud
    r = radius_for(len(pts), 10)
    for c in qs:
        d = _scan(pts, c)
        np.testing.assert_allclose(ref.knn(c, 10), np.sort(d)[:10], rtol=0,
                                   atol=1e-12)
        np.testing.assert_array_equal(
            np.sort(ref.within(c, r)),
            np.flatnonzero(d <= float(np.float32(r))))


def test_exact_answers_read_nought(cloud):
    pts, ref, qs = cloud
    r = radius_for(len(pts), 10)
    for c in qs:
        d = _scan(pts, c)
        top = np.argsort(d)[:10]
        gap, bad = ref.knn_gap(c, d[top].astype(np.float32), top)
        assert bad == 0 and gap < 1e-7
        hit = np.flatnonzero(d <= float(np.float32(r)))
        buf = np.full(64, -1)
        buf[:len(hit)] = hit
        assert ref.within_gap(c, r, len(hit), buf) == (0.0, 0)


def test_wrong_answers_read_their_distance(cloud):
    pts, ref, qs = cloud
    c = qs[0]
    d = _scan(pts, c)
    top = np.argsort(d)[:11]
    # the 11th neighbour in place of the 10th
    wrong = np.concatenate([top[:9], top[10:11]])
    gap, bad = ref.knn_gap(c, d[wrong].astype(np.float32), wrong)
    assert bad == 0 and gap == pytest.approx(d[top[10]] - d[top[9]], abs=1e-7)
    gap, bad = ref.knn_gap(c, d[top[:10]], np.r_[top[:9], top[0]])
    assert bad == 1
    r = radius_for(len(pts), 10)
    hit = np.flatnonzero(d <= float(np.float32(r)))
    buf = np.full(64, -1)
    buf[:len(hit) - 1] = hit[1:]                   # one point left out
    gap, bad = ref.within_gap(c, r, len(hit) - 1, buf)
    assert bad == 0 and gap == pytest.approx(r - d[hit[0]], rel=1e-5)
    assert ref.within_gap(c, r, 65, np.arange(64))[1] == 1   # overflow


def test_bfloat16_control_fails_the_comparison():
    rng = np.random.default_rng(8)
    pts = uniform_points(rng, 1 << 16)
    ref = Reference(pts)
    r = radius_for(len(pts), 10)
    gaps_k, gaps_w = [], []
    for c in rng.random((32, 3), dtype=np.float32):
        gaps_k.append(ref.knn_gap(c, *ref.control_knn(c, 10))[0])
        gaps_w.append(ref.within_gap(c, r, *ref.control_within(c, r, 64))[0])
    assert max(gaps_k) > 1e-4 and max(gaps_w) > 1e-4


def test_grid_over_side_by_side_boxes_matches_a_full_scan():
    from bench.data import rank_offsets
    rng = np.random.default_rng(9)
    pts = (uniform_points(rng, 4000).reshape(4, 1000, 3)
           + rank_offsets(4)[:, None, :]).reshape(-1, 3)
    ref = Reference(pts)
    assert tuple(ref.shape) == (128, 128, 64)
    r = radius_for(1000, 10)
    for c in pts[rng.integers(0, len(pts), 30)]:
        d = _scan(pts, c)
        np.testing.assert_allclose(ref.knn(c, 10), np.sort(d)[:10], rtol=0,
                                   atol=1e-12)
        np.testing.assert_array_equal(
            np.sort(ref.within(c, r)),
            np.flatnonzero(d <= float(np.float32(r))))

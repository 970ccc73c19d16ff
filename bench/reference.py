"""Plain numpy answers to kNN and radius queries, and the numbers by which
a served answer is compared with them.

The grid bucketing (``Reference`` up to ``_knn_candidates``) is copied from
``chip_smoke.py``, where ``tests/test_chip_smoke.py`` pins it to full-scan
answers; ``bench/tests/test_bench_reference.py`` pins this copy the same
way. It imports nothing of the library and takes nothing the library made:
it sees the points the benchmark generated and the queries it sent.

Each comparison returns two numbers per row, never raises:

* ``gap`` -- a distance in the cloud's units. kNN: the widest gap between
  a served distance, or the float64 distance of a served point, and the
  float64 reference distance of the same rank. Radius: the widest
  distance from the sphere's surface of a point on the wrong side (served
  but outside, or inside but not served); 0 when the sets agree.
* ``bad`` -- 1 when the answer breaks its own form: an index out of range
  or repeated, a radius buffer that overflowed or whose tail is not -1.

``control_*`` compute the same answers from bfloat16 coordinates (the
precision below the float32 the configurations state), to show that the
comparison fails an answer made one step less precisely.
"""
from __future__ import annotations

import math

import ml_dtypes
import numpy as np


def to_bf16(x) -> np.ndarray:
    """`x` rounded to bfloat16, held as float32."""
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


class Reference:
    """Plain numpy answers over one point set. The points are bucketed in
    a uniform grid of cells 1/GRID on a side over the unit boxes the set
    fills (the unit cube, or the ranks' boxes side by side; points outside
    fall in the border cells), so a check reads only the cells that can
    hold its answer instead of scanning all N points per query."""

    GRID = 64

    def __init__(self, pts: np.ndarray):
        self.pts = pts
        self.origin = np.round(pts.min(0).astype(np.float64))
        units = np.maximum(1, np.round(pts.max(0) - self.origin))
        self.shape = (units * self.GRID).astype(np.int64)
        key = self._key(self._cell(pts))
        self.order = np.argsort(key, kind="stable")
        self.starts = np.searchsorted(key[self.order],
                                      np.arange(int(np.prod(self.shape)) + 1))

    def _cell(self, x):
        """Grid cell of each point of `x`, clipped to the grid (monotone:
        a point inside a box lies in the cells between the corners')."""
        return np.clip(np.floor((np.asarray(x, np.float64) - self.origin)
                                * self.GRID), 0,
                       self.shape - 1).astype(np.int64)

    def _key(self, cells):
        g = self.shape
        return (cells[..., 0] * g[1] + cells[..., 1]) * g[2] + cells[..., 2]

    def _in_cells(self, cells):
        """Indices of the points in the given (M, 3) cells (clipped to
        the grid)."""
        keys = np.unique(self._key(np.clip(cells, 0, self.shape - 1)))
        s, e = self.starts[keys], self.starts[keys + 1]
        n = e - s
        first = np.repeat(s - (np.cumsum(n) - n), n)
        return self.order[first + np.arange(n.sum())]

    def _in_box(self, lo, hi):
        """Indices of a superset of the points in the box [lo, hi]."""
        a, b = self._cell(lo), self._cell(hi)
        axes = np.meshgrid(*[np.arange(a[j], b[j] + 1) for j in range(3)],
                           indexing="ij")
        return self._in_cells(np.stack(axes, -1).reshape(-1, 3))

    def _d2_f32(self, c, idx):
        return np.sum((self.pts[idx] - np.asarray(c, np.float32)) ** 2,
                      axis=1)

    def _dist64(self, c, idx):
        return np.linalg.norm(self.pts[idx].astype(np.float64)
                              - np.asarray(c, np.float64), axis=-1)

    def _knn_candidates(self, c, k):
        """Points whose float32 squared distance is within the k-th
        smallest (plus a tie band): grow a box around `c` until the
        band's radius fits inside it, so no point outside can be in it."""
        h = 1.0 / self.GRID
        while True:
            box = self._in_box(c - h, c + h)
            covers_all = len(box) == len(self.pts)
            if len(box) >= k:
                d2 = self._d2_f32(c, box)
                band = np.partition(d2, k - 1)[k - 1] * (1 + 1e-4) + 1e-12
                if covers_all or math.sqrt(band) * (1 + 1e-6) <= h:
                    return box[d2 <= band]
            h *= 2.0

    # -- answers --------------------------------------------------------------
    def knn(self, c, k: int) -> np.ndarray:
        """The k smallest float64 distances from `c`, ascending."""
        cand = self._knn_candidates(c, k)
        return np.sort(self._dist64(c, cand))[:k]

    def within(self, c, radius: float) -> np.ndarray:
        """Indices of the points at float64 distance <= radius (the radius
        as the float32 the query carried)."""
        r64 = float(np.float32(radius))
        box = self._in_box(c - r64 * 1.001, c + r64 * 1.001)
        return box[self._dist64(c, box) <= r64]

    # -- comparisons ----------------------------------------------------------
    def knn_gap(self, c, dists, idxs) -> tuple[float, int]:
        k = len(idxs)
        ref = self.knn(c, k)
        idxs = np.asarray(idxs)
        bad = int(np.any(idxs < 0) or np.any(idxs >= len(self.pts))
                  or len(set(idxs.tolist())) != k)
        gap = float(np.max(np.abs(np.asarray(dists, np.float64) - ref)))
        ok = (idxs >= 0) & (idxs < len(self.pts))
        if ok.all():
            own = np.sort(self._dist64(c, idxs))
            gap = max(gap, float(np.max(np.abs(own - ref))))
        return gap, bad

    def within_gap(self, c, radius: float, count: int, buf) -> tuple[float, int]:
        buf = np.asarray(buf)
        cap = len(buf)
        count = int(count)
        got = buf[:min(count, cap)]
        bad = int(count > cap or count < 0 or np.any(got < 0)
                  or np.any(got >= len(self.pts))
                  or len(set(got.tolist())) != len(got)
                  or np.any(buf[len(got):] != -1))
        got = got[(got >= 0) & (got < len(self.pts))]
        exact = self.within(c, radius)
        wrong = np.setxor1d(exact, got)
        if len(wrong) == 0:
            return 0.0, bad
        r64 = float(np.float32(radius))
        return float(np.max(np.abs(self._dist64(c, wrong) - r64))), bad

    # -- the control: the same answers from bfloat16 coordinates --------------
    def control_knn(self, c, k: int) -> tuple[np.ndarray, np.ndarray]:
        """(dists, idxs) of the k nearest by bfloat16 coordinates."""
        cand = self._knn_candidates(c, 3 * k)
        d = np.sqrt(np.sum((to_bf16(self.pts[cand]) - to_bf16(c)) ** 2,
                           axis=1))
        top = np.argsort(d, kind="stable")[:k]
        return d[top], cand[top]

    def control_within(self, c, radius: float, capacity: int):
        """(count, buf) of the points within a bfloat16 radius by
        bfloat16 coordinates."""
        r64 = float(np.float32(radius))
        box = self._in_box(c - r64 * 1.5, c + r64 * 1.5)
        d = np.sqrt(np.sum((to_bf16(self.pts[box]) - to_bf16(c)) ** 2,
                           axis=1))
        hit = box[d <= to_bf16(radius)]
        buf = np.full(capacity, -1, np.int64)
        buf[:min(len(hit), capacity)] = hit[:capacity]
        return len(hit), buf

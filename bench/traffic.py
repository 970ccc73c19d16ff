"""The one generator of requests: reads a mix (``bench/traffic/<mix>.json``)
and a configuration, and makes every query from the seed.

A mix holds:

* ``kind``: ``"knn"`` (k = the configuration's ``neighbors``) or
  ``"within"`` (radius holding ``neighbors`` points on average, buffer of
  the configuration's ``within_capacity``);
* ``loop``: ``"closed"`` -- ``clients`` clients, each with one request of
  ``rows`` query rows outstanding -- or ``"open"`` -- requests due at
  Poisson arrivals of ``rate_rps`` per second, with ``rows`` either a
  number or ``{"loguniform": [lo, hi]}``;
* ``updates`` (optional): ``{"every_s": s, "first_s": t, "spacing_share": f}``
  -- at t, t + s, ... the whole cloud moves by a normal step of f of the
  mean spacing and is handed to ``update_index``;
* ``limits``: the limit of each number the correctness check compares.

Every seed gets the same set of request sizes and gaps between arrivals,
in another order, so that a seed changes where the work falls and not how
much of it there is.

The configuration places the points: ``points`` uniform in the unit cube
(``filled_box``), or, with ``ranks`` > 1, ``points / ranks`` uniform in
each rank's unit box, the boxes laid out by ``partition_dim`` and
``shift`` as ArborX's ``distributed_tree_driver`` lays them out and
concatenated in rank order (so that sharding by input order puts one box
on each chip). Its ``queries`` say where query centres come from:
``"filled_box"``, uniform over the same boxes as the points, or
``"values"``, points of the cloud itself (the distributed driver's
default). Either way a request's rows are split evenly over the ranks, in
rank order. The radius holds ``neighbors`` points on average at one
rank's density.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from . import data


@dataclasses.dataclass(frozen=True)
class Due:
    t: float          # seconds after the window opens
    rows: int


class Traffic:
    def __init__(self, mix: dict, config: dict, seed: int):
        self.mix = mix
        self.config = config
        self.seed = int(seed)
        self.kind = mix["kind"]
        self.loop = mix["loop"]
        if self.kind not in ("knn", "within"):
            raise ValueError(f"mix kind {self.kind!r} is not knn or within")
        if self.loop not in ("closed", "open"):
            raise ValueError(f"mix loop {self.loop!r} is not closed or open")
        self.k = int(config["neighbors"])
        self.ranks = int(config.get("ranks", 1))
        self.dim = int(config["dim"])
        self.offsets = data.rank_offsets(
            self.ranks, int(config.get("partition_dim", 3)),
            float(config.get("shift", 1.0)))[:, :self.dim]
        self.radius = data.radius_for(int(config["points"]) // self.ranks,
                                      int(config["neighbors"]))
        self.query_from = config.get("queries", "filled_box")
        if self.query_from not in ("filled_box", "values"):
            raise ValueError(f"queries {self.query_from!r} is not "
                             "filled_box or values")
        self._values = None

    def rng(self, *stream: int) -> np.random.Generator:
        """An independent generator for one stream of this seed."""
        s = self.seed % (1 << 64)
        return np.random.default_rng([s & 0xFFFFFFFF, s >> 32, *stream])

    # -- data -----------------------------------------------------------------
    def points(self) -> np.ndarray:
        """The cloud: each rank's box in turn."""
        per = int(self.config["points"]) // self.ranks
        pts = data.uniform_points(self.rng(0), per * self.ranks, self.dim)
        if self.ranks > 1:
            pts = (pts.reshape(self.ranks, per, self.dim)
                   + self.offsets[:, None, :]).reshape(-1, self.dim)
        self._values = pts
        return pts

    def moves(self, pts: np.ndarray, count: int) -> list[np.ndarray]:
        """`count` successive moved copies of the cloud (a random walk)."""
        share = float(self.mix["updates"]["spacing_share"])
        rng, out = self.rng(1), []
        for _ in range(count):
            pts = data.jittered(rng, pts, share)
            out.append(pts)
        return out

    def queries(self, rng: np.random.Generator, m: int) -> np.ndarray:
        """`m` query centres, the first m / ranks in rank 0's box, and so
        on (``points`` has to have run where they are drawn from it)."""
        rank = np.arange(m) * self.ranks // m
        if self.query_from == "values":
            per = len(self._values) // self.ranks
            return self._values[rank * per + rng.integers(0, per, m)]
        a = rng.random((m, self.dim), dtype=np.float32)
        return a + self.offsets[rank] if self.ranks > 1 else a

    # -- schedules ------------------------------------------------------------
    def row_sizes(self) -> list[int]:
        """Every query-row count a request of this mix can have."""
        rows = self.mix["rows"]
        if isinstance(rows, int):
            return [rows]
        lo, hi = rows["loguniform"]
        return list(range(int(lo), int(hi) + 1))

    def schedule(self, seconds: float) -> list[Due]:
        """Open loop: the requests due in a window of `seconds`."""
        n = max(1, int(round(float(self.mix["rate_rps"]) * seconds)))
        q = (np.arange(n) + 0.5) / n
        gaps = -np.log1p(-q) * (seconds / n)    # exponential quantiles,
        gaps *= seconds / gaps.sum()            # scaled to fill the window
        rows = self.mix["rows"]
        if isinstance(rows, int):
            sizes = np.full(n, rows)
        else:
            lo, hi = rows["loguniform"]
            sizes = np.floor(np.exp(np.log(lo) + q * (np.log(hi + 1)
                                                      - np.log(lo))))
            sizes = np.clip(sizes, lo, hi).astype(int)
        rng = self.rng(2)
        gaps = rng.permutation(gaps)
        sizes = rng.permutation(sizes)
        t = np.cumsum(gaps) - gaps[0]
        return [Due(float(ti), int(si)) for ti, si in zip(t, sizes)]

    def update_times(self, seconds: float) -> list[float]:
        up = self.mix.get("updates")
        if not up:
            return []
        return [float(t) for t in np.arange(float(up["first_s"]), seconds,
                                            float(up["every_s"]))]

    def buckets(self, min_bucket: int, max_bucket: int) -> list[int]:
        """The bucket shapes this mix's batches can take."""
        def bucket(q):
            return max(min_bucket, 1 << max(q - 1, 0).bit_length())
        sizes = self.row_sizes()
        if self.loop == "closed":
            return sorted({bucket(min(s, max_bucket)) for s in sizes})
        lo = bucket(min(sizes))
        return [b for b in (1 << i for i in range(31))
                if lo <= b <= bucket(max_bucket)]

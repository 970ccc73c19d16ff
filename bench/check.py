"""Decide ``correct``: compare what the window's clients received with the
plain reference, once the window has closed.

A sample of the answered query rows, drawn from the seed, is compared
(``bench.reference``) with the points of the index version that answered
it (``RequestStats.index_version``): every version the benchmark
published is known, so an answer after a refit is checked against the
moved cloud, and an answer from a version it never published is wrong.

The numbers compared, each against the limit the mix states:

* ``dist_gap`` -- the widest reference gap over the sampled rows;
* ``bad_rows`` -- sampled rows whose answer breaks its form or names an
  unknown version (limit 0);
* ``missing`` -- requests sent in the window with no answer, or whose
  answer was an error, and updates that never became visible (limit 0).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from .reference import Reference

#: the order in which the numbers are printed
NAMES = ("dist_gap", "bad_rows", "missing")


@dataclasses.dataclass
class Check:
    values: dict
    limits: dict
    rows_checked: int

    @property
    def correct(self) -> bool:
        return all(self.values[k] <= self.limits[k] for k in NAMES)

    def as_json(self) -> dict:
        return {k: {"value": self.values[k], "limit": self.limits[k]}
                for k in NAMES}

    def lines(self) -> list[str]:
        return [f"check {k}: {self.values[k]!r} limit {self.limits[k]!r}"
                for k in NAMES]


def sample_rows(window, rng: np.random.Generator, n: int) -> list[tuple]:
    """Up to `n` (request, row) pairs of the answered requests."""
    answered = [r for r in window.sent if r.answered]
    sizes = np.array([r.rows for r in answered], np.int64)
    total = int(sizes.sum())
    if total == 0:
        return []
    picks = np.sort(rng.choice(total, min(n, total), replace=False))
    starts = np.cumsum(sizes) - sizes
    which = np.searchsorted(starts, picks, side="right") - 1
    return [(answered[w], int(p - starts[w])) for w, p in zip(which, picks)]


def compare(window, traffic, versions: dict, limits: dict, rows: int,
            control: bool = False) -> Check:
    """`versions` maps each published version number to its points. With
    `control`, the answers compared are the bfloat16 reference's for the
    same rows, put in the program's place."""
    refs: dict[int, Reference] = {}       # by id() of the points
    gap, bad = 0.0, 0
    picks = sample_rows(window, traffic.rng(4), rows)
    for rec, j in picks:
        st = rec.resp.stats
        pts = versions.get(st.index_version)
        if pts is None:
            bad += 1
            continue
        ref = refs.get(id(pts))
        if ref is None:
            ref = refs[id(pts)] = Reference(pts)
        c = rec.a[j]
        if traffic.kind == "knn":
            if control:
                d, i = ref.control_knn(c, traffic.k)
            else:
                d, i = rec.resp.dists[j], rec.resp.idxs[j]
            g, b = ref.knn_gap(c, d, i)
        else:
            if control:
                n, buf = ref.control_within(c, traffic.radius,
                                            rec.resp.idxs.shape[1])
            else:
                n, buf = rec.resp.counts[j], rec.resp.idxs[j]
            g, b = ref.within_gap(c, traffic.radius, n, buf)
        gap, bad = max(gap, g), bad + b
    missing = sum(not r.answered or r.error is not None for r in window.sent)
    missing += sum(m.error is not None for m in window.moved)
    return Check({"dist_gap": gap, "bad_rows": bad, "missing": missing},
                 {k: limits[k] for k in NAMES}, len(picks))

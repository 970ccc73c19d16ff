"""Point clouds and moved copies, made from the seed on the host.

``uniform_points``, ``radius_for`` and ``jittered`` are copied from
``chip_smoke.py`` (the bring-up script) so that the benchmark's data does
not move when that script does. ``rank_offsets`` places the ranks' boxes
as ArborX's ``distributed_tree_driver`` does.
"""
from __future__ import annotations

import math

import numpy as np


def uniform_points(rng: np.random.Generator, n: int, dim: int = 3) -> np.ndarray:
    """`n` points uniform in the unit cube (ArborX ``filled_box``)."""
    return rng.random((n, dim), dtype=np.float32)


def rank_offsets(ranks: int, partition_dim: int = 3,
                 shift: float = 1.0) -> np.ndarray:
    """(ranks, 3) corner of each rank's unit box. As ArborX's
    ``distributed_tree_driver`` lays them out: the ranks fill a line
    (`partition_dim` 1), a square board (2) or a cube (3) of boxes, rank
    r at (r mod i, (r div i) mod i, r div i^2) with i the side of that
    arrangement, `shift` box sides apart (1: the boxes touch)."""
    if partition_dim == 1:
        i = ranks
    elif partition_dim == 2:
        i = math.ceil(math.sqrt(ranks))
    elif partition_dim == 3:
        i = math.ceil(round(ranks ** (1.0 / 3.0), 9))
    else:
        raise ValueError(f"partition_dim {partition_dim} is not 1, 2 or 3")
    r = np.arange(ranks)
    cells = np.stack([r % i, (r // i) % i if partition_dim > 1 else 0 * r,
                      r // (i * i) if partition_dim > 2 else 0 * r], 1)
    return (shift * cells).astype(np.float32)


def radius_for(n: int, neighbours: int) -> float:
    """Ball radius holding `neighbours` points on average at density n."""
    return float((3.0 * neighbours / (4.0 * math.pi * n)) ** (1.0 / 3.0))


def jittered(rng: np.random.Generator, pts: np.ndarray,
             spacing_share: float = 0.1) -> np.ndarray:
    """Moved copy of `pts`: a normal step of `spacing_share` of the mean
    spacing, small enough that the store keeps the update a refit."""
    sigma = spacing_share * len(pts) ** (-1.0 / 3.0)
    step = rng.standard_normal(pts.shape, dtype=np.float32)
    return (pts + np.float32(sigma) * step).astype(np.float32)

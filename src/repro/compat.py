"""The sharding names the rest of the repo (and the test subprocesses)
import from one place, spelled as jax 0.9 spells them.

``make_mesh`` keeps jax's default axis types (``Explicit`` in jax 0.9);
callers that want sharding left out of array types pass
``axis_types=(AxisType.Auto, ...)``.
"""
from __future__ import annotations

from jax import make_mesh, shard_map
from jax.sharding import AxisType

__all__ = ["AxisType", "make_mesh", "shard_map"]

"""ArborX 2.0 in JAX: performance-portable geometric search (the paper's
primary contribution). See DESIGN.md for the GPU->TPU adaptation map.

The front door is the unified Index protocol (DESIGN.md §6): BVH,
BruteForce, and DistributedTree all construct from (values,
indexable_getter, policy) and answer one polymorphic ``query()``."""
from . import access, callbacks, engine, geometry, morton, predicates, traversal
from .brute_force import BruteForce
from .bvh import BVH
from .dbscan import dbscan
from .distributed import DistributedTree
from .engine import EngineConfig, QueryEngine, default_engine, set_default_engine
from .emst import emst
from .index import ExecutionPolicy, Index, QueryResult
from .interpolation import mls_interpolate
from .lbvh import LBVH, build, refit, sah_cost
from .predicates import intersects, nearest
from .raytracing import cast_intersect, cast_nearest, cast_ordered

__all__ = [
    "Index", "ExecutionPolicy", "QueryResult",
    "BVH", "BruteForce", "DistributedTree", "LBVH", "build", "refit",
    "sah_cost",
    "QueryEngine", "EngineConfig", "default_engine", "set_default_engine",
    "intersects", "nearest", "dbscan", "emst", "mls_interpolate",
    "cast_nearest", "cast_intersect", "cast_ordered",
    "access", "callbacks", "engine", "geometry", "morton", "predicates",
    "traversal",
]

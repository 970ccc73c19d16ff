"""Predicates (ArborX API v2): ``intersects``, ``nearest``, and the ray
predicates (§2.5). A predicate array is a pytree of N predicates of the same
kind, mirroring ``Kokkos::View<decltype(ArborX::intersects(Point{}))*>``.

Each predicate kind knows how to test itself against an internal-node AABB
(for pruning) and against leaf values (via the distance/intersection kernels
in :mod:`repro.core.geometry`).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from . import geometry as G

__all__ = ["Intersects", "Nearest", "RayNearest", "RayIntersect",
           "RayOrderedIntersect", "intersects", "nearest", "attach_data"]


def _register(cls=None, static=()):
    """Register a predicate dataclass as a pytree; `static` fields go into
    aux_data (they are Python ints like `k`, not arrays)."""
    def wrap(cls):
        cls = dataclasses.dataclass(frozen=True)(cls)
        fields = [f.name for f in dataclasses.fields(cls)]
        dyn = [f for f in fields if f not in static]

        def flatten(obj):
            return (tuple(getattr(obj, f) for f in dyn),
                    tuple(getattr(obj, f) for f in static))

        def unflatten(aux, children):
            return cls(**dict(zip(dyn, children)), **dict(zip(static, aux)))

        jax.tree_util.register_pytree_node(cls, flatten, unflatten)
        return cls
    if cls is not None:
        return wrap(cls)
    return wrap


@_register
class Intersects:
    """Spatial predicate: match values whose geometry intersects `geom`.

    ``data`` carries optional per-predicate payload (ArborX ``attach``),
    delivered to callbacks.
    """
    geom: object          # geometry array (Points/Boxes/Spheres/...)
    data: object = None

    def __len__(self):
        return len(self.geom)


@_register(static=("k",))
class Nearest:
    """k-nearest predicate. `geom` is the query geometry array, `k` static.

    ``exclude`` is an optional ``(query_labels, leaf_labels)`` pair of
    int32 arrays ((Q,) and (N,), in ORIGINAL index space): a stored value
    is a candidate for query q only when
    ``leaf_labels[value_index] != query_labels[q]`` — Borůvka's "nearest
    outside my component" query (§2.4 EMST). Backends that cannot honor
    it (DistributedTree) must not be routed such
    predicates; the loop/bruteforce paths implement it exactly.
    """
    geom: object
    k: int = 1
    data: object = None
    exclude: object = None

    def __len__(self):
        return len(self.geom)


@_register(static=("k",))
class RayNearest:
    """First-k ray hits (§2.5 'nearest'; k=1 -> closest object)."""
    rays: G.Rays
    k: int = 1
    data: object = None

    def __len__(self):
        return len(self.rays)


@_register
class RayIntersect:
    """All ray hits (§2.5 'intersect' — k = infinity, transparent objects)."""
    rays: G.Rays
    data: object = None

    def __len__(self):
        return len(self.rays)


@_register
class RayOrderedIntersect:
    """All ray hits ordered by distance along the ray (§2.5)."""
    rays: G.Rays
    data: object = None

    def __len__(self):
        return len(self.rays)


def intersects(geom, data=None) -> Intersects:
    """ArborX::intersects — works for any geometry array.

    ``intersects(Sphere(center, r))`` is the API-v2 spelling of the old
    ``within(point, r)``.
    """
    return Intersects(geom, data)


def nearest(geom, k: int = 1, data=None, exclude=None) -> Nearest:
    return Nearest(geom, k, data, exclude)


def attach_data(pred, data):
    """ArborX::attach analogue: attach payload to an existing predicate."""
    return dataclasses.replace(pred, data=data)


# ---------------------------------------------------------------------------
# Node-vs-predicate tests used by traversal for subtree pruning.
# All take a SINGLE predicate (unbatched leaves) + a batch of node boxes
# (M, dim)/(M, dim) and return (M,) bool or float.
# ---------------------------------------------------------------------------

def node_overlap_test(pred, lo, hi):
    """(M,) bool: may the subtree under box [lo,hi] contain matches?"""
    g = pred.geom if isinstance(pred, (Intersects, Nearest)) else None
    if isinstance(pred, Intersects):
        if isinstance(g, G.Points):
            return G.intersects_box_point(lo, hi, g.coords)
        if isinstance(g, G.Boxes):
            return G.intersects_box_box(g.lo, g.hi, lo, hi)
        if isinstance(g, G.Spheres):
            return G.intersects_box_sphere(lo, hi, g.center, g.radius)
        if isinstance(g, (G.Triangles, G.Segments, G.Tetrahedra)):
            b = G.to_boxes(g)
            return G.intersects_box_box(b.lo, b.hi, lo, hi)
        raise TypeError(f"no overlap test for {type(g).__name__}")
    if isinstance(pred, (RayNearest, RayIntersect, RayOrderedIntersect)):
        hit, _ = G.ray_box(pred.rays.origin, pred.rays.direction, lo, hi)
        return hit
    raise TypeError(f"no overlap test for predicate {type(pred).__name__}")


def node_min_distance(pred, lo, hi):
    """(M,) float: lower bound of distance from the query to box [lo,hi].

    For ray predicates the "distance" is the ray parameter t at box entry,
    so first-k-hits traversal (§2.5 `nearest`) reuses the kNN machinery.
    """
    if isinstance(pred, (RayNearest, RayIntersect, RayOrderedIntersect)):
        _, t_enter = G.ray_box(pred.rays.origin, pred.rays.direction, lo, hi)
        return t_enter
    g = pred.geom
    if isinstance(g, G.Points):
        return G.distance_point_box(g.coords, lo, hi)
    if isinstance(g, G.Spheres):
        return jnp.maximum(G.distance_point_box(g.center, lo, hi) - g.radius, 0.0)
    if isinstance(g, G.Boxes):
        # box-to-box distance
        d = jnp.maximum(jnp.maximum(lo - g.hi, g.lo - hi), 0.0)
        return jnp.sqrt(jnp.sum(d * d, axis=-1))
    c = G.centroid(g)
    return G.distance_point_box(c, lo, hi)


def leaf_match_test(pred, values):
    """(L,) bool for Intersects: exact (fine) test against leaf values."""
    g = pred.geom
    if isinstance(pred, Intersects):
        if isinstance(g, G.Points):
            if isinstance(values, G.Boxes):
                return G.intersects_box_point(values.lo, values.hi, g.coords)
            if isinstance(values, G.Points):
                return jnp.all(values.coords == g.coords, axis=-1)
            if isinstance(values, G.Spheres):
                return G.distance_point_point(g.coords, values.center) <= values.radius
            if isinstance(values, G.Triangles):
                return G.point_in_triangle(g.coords, values.a, values.b, values.c)
            if isinstance(values, G.Tetrahedra):
                return G.point_in_tetrahedron(g.coords, values.a, values.b, values.c, values.d)
        if isinstance(g, G.Spheres):
            if isinstance(values, G.Points):
                return G.distance_point_point(g.center, values.coords) <= g.radius
            if isinstance(values, G.Boxes):
                return G.intersects_box_sphere(values.lo, values.hi, g.center, g.radius)
            if isinstance(values, G.Spheres):
                return (G.distance_point_point(g.center, values.center)
                        <= g.radius + values.radius)
            if isinstance(values, G.Triangles):
                return G.distance_point_triangle(g.center, values.a, values.b, values.c) <= g.radius
            if isinstance(values, G.Segments):
                return G.distance_point_segment(g.center, values.a, values.b) <= g.radius
        if isinstance(g, G.Boxes):
            vb = G.to_boxes(values)
            return G.intersects_box_box(g.lo, g.hi, vb.lo, vb.hi)
        vb = G.to_boxes(values)
        gb = G.to_boxes(g)
        return G.intersects_box_box(gb.lo, gb.hi, vb.lo, vb.hi)
    raise TypeError(f"no leaf test for {type(pred).__name__}")


def leaf_distance(pred, values):
    """(L,) float: FINE distance from query geometry to leaf values (§2.1.2:
    fine nearest-neighbor search — distances to user data, not to boxes).

    For ray predicates returns the hit parameter t (inf on miss)."""
    if isinstance(pred, (RayNearest, RayIntersect, RayOrderedIntersect)):
        _, t = leaf_ray_hit(pred, values)
        return t
    g = pred.geom
    q = G.centroid(g) if not isinstance(g, G.Points) else g.coords
    if isinstance(values, G.Points):
        return G.distance_point_point(q, values.coords)
    if isinstance(values, G.Boxes):
        return G.distance_point_box(q, values.lo, values.hi)
    if isinstance(values, G.Spheres):
        return G.distance_point_sphere(q, values.center, values.radius)
    if isinstance(values, G.Triangles):
        return G.distance_point_triangle(q, values.a, values.b, values.c)
    if isinstance(values, G.Segments):
        return G.distance_point_segment(q, values.a, values.b)
    vb = G.to_boxes(values)
    return G.distance_point_box(q, vb.lo, vb.hi)


def leaf_ray_hit(pred, values):
    """(L,) (hit, t) for ray predicates against leaf values."""
    r = pred.rays
    if isinstance(values, G.Boxes):
        return G.ray_box(r.origin, r.direction, values.lo, values.hi)
    if isinstance(values, G.Spheres):
        return G.ray_sphere(r.origin, r.direction, values.center, values.radius)
    if isinstance(values, G.Triangles):
        return G.ray_triangle(r.origin, r.direction, values.a, values.b, values.c)
    if isinstance(values, G.Points):
        b = G.to_boxes(values)
        return G.ray_box(r.origin, r.direction, b.lo, b.hi)
    raise TypeError(f"ray tracing unsupported for {type(values).__name__} "
                    "(§2.5: box, triangle, sphere)")

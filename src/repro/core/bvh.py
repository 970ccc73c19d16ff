"""ArborX API v2 ``BVH`` (§2.1.3), an :class:`~repro.core.index.Index`.

The C++ template parameters map to Python as:
  MemorySpace      -> JAX device (``ExecutionPolicy.device``)
  Value            -> any pytree-of-arrays container ("values")
  IndexableGetter  -> callable values -> Boxes (bounding volumes)
  BoundingVolume   -> AABB (k-DOP support via indexable getters that return
                      enlarged boxes; the traversal only needs lo/hi)

Construction is ``BVH(values, indexable_getter=..., policy=...)``; the
API v1 per-call execution-space argument is absorbed into the policy.
All query flavors go through the inherited polymorphic
:meth:`~repro.core.index.Index.query`; this class only implements the
backend SPI — engine-dispatched count/fill/kNN plus the N < 2 linear-scan
fallbacks.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from . import callbacks as CB
from . import engine as E
from . import geometry as G
from . import lbvh
from . import predicates as P
from . import traversal as T
from .access import default_indexable_getter
from .index import ExecutionPolicy, Index, QueryResult, _bcast_state, _warn_deprecated

__all__ = ["BVH", "QueryResult"]

_DEVICE_TYPES = (jax.Device,) if hasattr(jax, "Device") else ()


def _is_legacy_space(arg):
    """The API v1 constructors took (space, values, ...): a leading None or
    jax.Device marks the old spelling (new-style values are never None)."""
    return arg is None or (bool(_DEVICE_TYPES)
                           and isinstance(arg, _DEVICE_TYPES))


class BVH(Index):
    def __init__(self, values, indexable_getter=default_indexable_getter,
                 *_legacy, policy: ExecutionPolicy | None = None, engine=None,
                 bits: int = 64, refit: str = "rmq",
                 build_engine: str | None = None):
        if _is_legacy_space(values):
            _warn_deprecated(
                "BVH.__init__", "BVH(space, values, ...) is deprecated; "
                "use BVH(values, indexable_getter=..., policy="
                "ExecutionPolicy(device=space))")
            space, values = values, indexable_getter
            indexable_getter = _legacy[0] if _legacy else default_indexable_getter
            policy = (policy or ExecutionPolicy()).override(device=space)
        elif _legacy:
            raise TypeError("BVH() takes at most 2 positional arguments "
                            "(values, indexable_getter)")
        self._init_common(values, indexable_getter, policy, engine)
        if build_engine is not None:
            self.policy = self.policy.override(build_engine=build_engine)
        if self._n >= 2:
            self.tree = lbvh.build(self._boxes, bits=bits, refit=refit,
                                   engine=self.policy.build_engine or "auto")
            if self.policy.device is not None:
                self.tree = jax.device_put(self.tree, self.policy.device)
        else:
            self.tree = None  # degenerate; queries fall back to linear scan

    @classmethod
    def from_tree(cls, values, tree, indexable_getter=default_indexable_getter,
                  *_legacy, policy: ExecutionPolicy | None = None, engine=None):
        """Wrap an existing LBVH over (possibly moved) values without
        rebuilding — the swap-in constructor for ``lbvh.refit`` output.
        The caller guarantees `tree` bounds `indexable_getter(values)`."""
        if _is_legacy_space(values):
            _warn_deprecated(
                "BVH.from_tree", "BVH.from_tree(space, values, tree) is "
                "deprecated; use BVH.from_tree(values, tree, policy=...)")
            space, values, tree = values, tree, indexable_getter
            indexable_getter = _legacy[0] if _legacy else default_indexable_getter
            policy = (policy or ExecutionPolicy()).override(device=space)
        elif _legacy:
            raise TypeError("BVH.from_tree() takes at most 3 positional "
                            "arguments (values, tree, indexable_getter)")
        obj = cls.__new__(cls)
        obj._init_common(values, indexable_getter, policy, engine)
        obj.tree = tree if obj.policy.device is None else \
            jax.device_put(tree, obj.policy.device)
        return obj

    def _init_common(self, values, indexable_getter, policy, engine):
        self.policy = policy or ExecutionPolicy()
        if engine is not None:
            self.policy = self.policy.override(engine=engine)
        self.values = values
        self._getter = indexable_getter
        boxes = indexable_getter(values)
        self._n = len(boxes)
        self._boxes = boxes
        self._bf = None

    @property
    def space(self):
        """API v1 compatibility alias for ``policy.device``."""
        return self.policy.device

    @property
    def _engine(self):
        return self.policy.resolve_engine()

    def _brute(self):
        """Lazy MXU-path sibling index over the same values (engine route)."""
        if self._bf is None:
            from .brute_force import BruteForce
            self._bf = BruteForce(self.values, self._getter, policy=self.policy)
        return self._bf

    # --- container interface (§2.1.3) -----------------------------------
    def size(self) -> int:
        return self._n

    def bounds(self) -> G.Boxes:
        if self.tree is None:
            return G.merge_boxes(self._boxes) if self._n else G.Boxes(
                jnp.zeros((1, 0)), jnp.zeros((1, 0)))
        return G.Boxes(self.tree.node_lo[:1], self.tree.node_hi[:1])

    # --- backend SPI ------------------------------------------------------
    def _query_callback_impl(self, predicates, callback, state0, pol):
        """Callback flavor: the while loop runs the callback on every match
        in traversal order (CSR never materialized)."""
        if self.tree is None:
            return _degenerate_callback(self.values, self._boxes, self._n,
                                        predicates, callback, state0)
        return T.traverse(self.tree, self.values, predicates, callback, state0)

    def _count_impl(self, predicates, pol):
        """Per-query match counts, dispatched by the engine (DESIGN.md §3):
        MXU all-pairs or the vmapped while loop. Both produce identical
        int32 counts."""
        engine = pol.resolve_engine()
        if (self.tree is not None and engine.route_spatial(self, predicates)
                == E.ROUTE_BRUTEFORCE):
            return self._brute()._count_impl(predicates, pol)
        cb, s0 = CB.counting()
        return self._query_callback_impl(predicates, cb,
                                         _bcast_state(s0, len(predicates)), pol)

    def _fill_impl(self, predicates, capacity, pol):
        """(counts, idx_buf (Q, capacity)): full counts plus the first
        `capacity` matched indices per query (engine-dispatched; the match
        SET per query is path-independent, the buffer order is not)."""
        engine = pol.resolve_engine()
        if (self.tree is not None
                and engine.route_spatial(self, predicates, capacity)
                == E.ROUTE_BRUTEFORCE):
            return self._brute()._fill_impl(predicates, capacity, pol)
        cb, s0 = CB.collect_hits(capacity)
        count, idxs, _ = self._query_callback_impl(
            predicates, cb, _bcast_state(s0, len(predicates)), pol)
        return count, idxs

    def _csr_exact(self, predicates, pol):
        """Unclamped + brute-force route: one-pass CSR (the two-pass
        count->fill would run the (Q, N) match matrix twice)."""
        engine = pol.resolve_engine()
        if (self.tree is not None and isinstance(predicates, P.Intersects)
                and engine.route_spatial(self, predicates) == E.ROUTE_BRUTEFORCE):
            return self._brute()._csr_exact(predicates, pol)
        return None

    def _knn_impl(self, predicates, pol):
        """(dists, idxs) (N_q, k) for Nearest / RayNearest predicates,
        engine-dispatched like counts. Nearest.exclude (the EMST
        component filter) pins the exact loop path."""
        k = predicates.k
        if isinstance(predicates, P.Nearest) and predicates.exclude is not None:
            ex_q, leaf_l = predicates.exclude
            plain = dataclasses.replace(predicates, exclude=None)
            if self.tree is None:
                from .brute_force import BruteForce
                return BruteForce(self.values, self._getter)._knn_impl(
                    predicates, pol)
            return T.traverse_knn(self.tree, self.values, plain, k,
                                  exclude_labels=ex_q, leaf_labels=leaf_l)
        if self.tree is None:
            return _degenerate_knn(self.values, self._boxes, self._n,
                                   predicates, k)
        if pol.resolve_engine().route_knn(self, predicates) == \
                E.ROUTE_BRUTEFORCE:
            return self._brute()._knn_impl(predicates, pol)
        return T.traverse_knn(self.tree, self.values, predicates, k)


# --- degenerate N in {0, 1}: linear scan ---------------------------------

def _degenerate_callback(values, boxes, n, predicates, callback, init_state):
    def one(pred, st):
        if n == 0:
            return st
        val = T.value_at(values, 0)
        fine, t = T._leaf_test(pred, val)
        new_state, _ = callback(st, pred, val, jnp.int32(0), t)
        return T.tree_select(fine, new_state, st)
    return jax.vmap(one)(predicates, init_state)


def _degenerate_knn(values, boxes, n, predicates, k):
    def one(pred):
        dists = jnp.full((k,), jnp.inf)
        idxs = jnp.full((k,), -1, jnp.int32)
        if n == 0:
            return dists, idxs
        val = T.value_at(values, 0)
        d = P.leaf_distance(pred, T._as_batch1(val))[0]
        hit = jnp.isfinite(d)
        return (dists.at[0].set(d),
                idxs.at[0].set(jnp.where(hit, jnp.int32(0), jnp.int32(-1))))
    return jax.vmap(one)(predicates)

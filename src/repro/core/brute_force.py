"""Brute-force search index (ArborX 2.0 §1: "New brute-force search
structure"), an :class:`~repro.core.index.Index` — drop-in for BVH.

On GPU ArborX tiles all-pairs tests over thread blocks. On TPU this
structure is *more* attractive than on GPU (DESIGN.md §2): the pairwise
distance matrix is a matmul

    ||x - y||^2 = ||x||^2 - 2 x.y^T + ||y||^2

that runs on the MXU at matmul throughput, while the BVH traversal runs on
the VPU. The crossover point between BruteForce and BVH therefore sits at
much larger N on TPU; the engine's ``BF_MAX_WORK`` (DESIGN.md §3) places it.

The pure-JAX implementation below tiles queries into blocks of `block_q` so
the (Q, N) distance matrix never materializes. The Pallas kernel variant
(repro.kernels.bruteforce_knn) additionally tiles N into VMEM-resident
panels with a streaming top-k merge.

Exact by construction — serves as the oracle for the BVH in tests.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import geometry as G
from . import predicates as P
from .access import default_indexable_getter
from .index import ExecutionPolicy, Index, QueryResult, _warn_deprecated
from .traversal import tree_select, value_at

__all__ = ["BruteForce", "pairwise_sq_distances"]


def pairwise_sq_distances(x: jax.Array, y: jax.Array) -> jax.Array:
    """(Q, N) squared euclidean distances via the MXU-friendly expansion.
    The product runs at HIGHEST precision: a TPU's default float32
    matmul is one bfloat16 pass, whose ~1e-2 error would pick the wrong
    neighbours."""
    x2 = jnp.sum(x * x, axis=-1, keepdims=True)          # (Q, 1)
    y2 = jnp.sum(y * y, axis=-1, keepdims=True).T        # (1, N)
    xy = jnp.matmul(x, y.T, precision=jax.lax.Precision.HIGHEST)  # MXU
    return jnp.maximum(x2 - 2.0 * xy + y2, 0.0)


class BruteForce(Index):
    """Stores values; queries evaluate the predicate against every value."""

    def __init__(self, values, indexable_getter=default_indexable_getter,
                 *_legacy, policy: ExecutionPolicy | None = None,
                 block_q: int = 256):
        from .bvh import _is_legacy_space
        if _is_legacy_space(values):
            _warn_deprecated(
                "BruteForce.__init__", "BruteForce(space, values, ...) is "
                "deprecated; use BruteForce(values, indexable_getter=..., "
                "policy=ExecutionPolicy(device=space))")
            space, values = values, indexable_getter
            indexable_getter = _legacy[0] if _legacy else default_indexable_getter
            policy = (policy or ExecutionPolicy()).override(device=space)
        elif _legacy:
            raise TypeError("BruteForce() takes at most 2 positional "
                            "arguments (values, indexable_getter)")
        self.policy = policy or ExecutionPolicy()
        self.values = values
        self._getter = indexable_getter
        self._boxes = indexable_getter(values)
        self._n = len(self._boxes)
        self._block_q = block_q

    @property
    def space(self):
        return self.policy.device

    def size(self) -> int:
        return self._n

    def bounds(self) -> G.Boxes:
        return G.merge_boxes(self._boxes)

    # --- backend SPI ------------------------------------------------------
    def _query_callback_impl(self, predicates, callback, state0, pol):
        """Apply `callback` on every match, in index order per query."""
        values = self.values
        n = self._n

        def one(pred, st):
            def body(i, carry):
                st, done = carry
                val = value_at(values, i)
                fine, t = _leaf_test1(pred, val)
                new_st, cb_done = callback(st, pred, val, i, t)
                hit = fine & ~done
                st = tree_select(hit, new_st, st)
                done = done | (hit & cb_done)
                return st, done

            st, _ = jax.lax.fori_loop(0, n, body, (st, jnp.bool_(False)))
            return st

        return jax.vmap(one)(predicates, state0)

    def _count_impl(self, predicates, pol):
        return self._match_matrix(predicates).sum(-1).astype(jnp.int32)

    def _csr_exact(self, predicates, pol):
        """One-pass exact CSR from the (Q, N) match matrix (the two-pass
        count->fill would build the matrix twice). Also serves
        RayIntersect: its match set is the hit test, same row-major
        ordering semantics."""
        if not isinstance(predicates, (P.Intersects, P.RayIntersect)):
            return None
        mask = self._match_matrix(predicates)            # (Q, N) bool
        counts = mask.sum(-1).astype(jnp.int32)
        offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                   jnp.cumsum(counts)]).astype(jnp.int32)
        total = int(offsets[-1])
        qid, idx = jnp.nonzero(mask, size=total, fill_value=0)
        # nonzero is row-major -> already CSR-ordered by query
        idx = idx.astype(jnp.int32)
        return QueryResult(values=value_at(self.values, idx), indices=idx,
                           offsets=offsets)

    def _fill_impl(self, predicates, capacity, pol):
        """The ``collect_hits`` contract from the match matrix: full counts
        plus the first `capacity` matched indices per query (index order)."""
        mask = self._match_matrix(predicates)            # (Q, N) bool
        counts = mask.sum(-1).astype(jnp.int32)
        n = mask.shape[1]
        key = jnp.where(mask, jnp.arange(n, dtype=jnp.int32)[None, :], n)
        first = jax.lax.sort(key, dimension=1)[:, :capacity]
        buf = jnp.where(first < n, first, -1).astype(jnp.int32)
        return counts, buf

    def _knn_impl(self, predicates, pol):
        """(dists, idxs): (Q, k) exact k-nearest by fine distance. Ray
        predicates rank by hit parameter t; misses come back (-1, inf),
        matching the traversal path."""
        import dataclasses
        k = predicates.k
        exclude = getattr(predicates, "exclude", None)
        if exclude is not None:
            predicates = dataclasses.replace(predicates, exclude=None)
        d = self._distance_matrix(predicates)            # (Q, N)
        if exclude is not None:
            ex_q, leaf_l = exclude
            d = jnp.where(leaf_l[None, :] == ex_q[:, None], jnp.inf, d)
        k_eff = min(k, self._n)
        neg_top, idx = jax.lax.top_k(-d, k_eff)
        dists = -neg_top
        idx = idx.astype(jnp.int32)
        if self._expanded(predicates):
            # the expansion selects; the k winners get the exact fine
            # distance (the loop path's formula), re-sorted by it
            g = predicates.geom.coords
            exact = G.distance_point_point(g[:, None, :],
                                           self.values.coords[idx])
            exact = jnp.where(jnp.isinf(dists), jnp.inf, exact)  # excluded
            order = jnp.argsort(exact, axis=1, stable=True)
            dists = jnp.take_along_axis(exact, order, 1)
            idx = jnp.take_along_axis(idx, order, 1)
        if k_eff < k:
            pad_d = jnp.full((d.shape[0], k - k_eff), jnp.inf, d.dtype)
            pad_i = jnp.full((d.shape[0], k - k_eff), -1, jnp.int32)
            dists = jnp.concatenate([dists, pad_d], -1)
            idx = jnp.concatenate([idx, pad_i], -1)
        # non-matches (ray misses, excluded leaves) carry d=inf: blank them
        idx = jnp.where(jnp.isinf(dists), -1, idx)
        return dists, idx

    # -- internals -----------------------------------------------------------
    def _match_matrix(self, predicates):
        """(Q, N) bool, blocked over queries to bound memory. Ray
        predicates match where the exact hit test succeeds."""
        values = self.values
        is_ray = isinstance(predicates, (P.RayNearest, P.RayIntersect,
                                         P.RayOrderedIntersect))

        def test(p):
            if is_ray:
                hit, _ = P.leaf_ray_hit(p, values)
                return hit
            return P.leaf_match_test(p, values)

        def block(pred_blk):
            return jax.vmap(test)(pred_blk)

        return _map_query_blocks(block, predicates, self._block_q)

    def _expanded(self, predicates) -> bool:
        """Point queries against Points values: the MXU expansion path."""
        return (isinstance(getattr(predicates, "geom", None), G.Points)
                and isinstance(self.values, G.Points))

    def _distance_matrix(self, predicates):
        values = self.values
        if self._expanded(predicates):
            # fast path: MXU expansion
            return jnp.sqrt(pairwise_sq_distances(predicates.geom.coords,
                                                  values.coords))

        def block(pred_blk):
            return jax.vmap(lambda p: P.leaf_distance(p, values))(pred_blk)

        return _map_query_blocks(block, predicates, self._block_q)


def _map_query_blocks(fn, predicates, block_q):
    nq = len(predicates)
    if nq <= block_q:
        return fn(predicates)
    out = []
    for s in range(0, nq, block_q):
        blk = jax.tree_util.tree_map(lambda a: a[s:s + block_q], predicates)
        out.append(fn(blk))
    return jnp.concatenate(out, axis=0)


def _leaf_test1(pred, val):
    """Single-value leaf test -> (bool scalar, t scalar)."""
    batched = jax.tree_util.tree_map(lambda a: a[None], val)
    if isinstance(pred, (P.RayNearest, P.RayIntersect, P.RayOrderedIntersect)):
        hit, t = P.leaf_ray_hit(pred, batched)
        return jnp.reshape(hit, ()), jnp.reshape(t, ())
    fine = P.leaf_match_test(pred, batched)
    return jnp.reshape(fine, ()), jnp.float32(0.0)

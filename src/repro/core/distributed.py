"""DistributedTree (§2.3): distributed search over a mesh axis, an
:class:`~repro.core.index.Index`.

ArborX's ``DistributedTree`` takes an ``MPI_Comm``; the SPMD analogue here
is a (mesh, axis) pair — ranks become shards of the named mesh axis and
two-sided MPI becomes ``jax.lax`` collectives inside ``shard_map``
(DESIGN.md §2). "GPU-aware MPI" needs no emulation: ICI collectives never
stage through host memory.

API v2 surface: construction takes ``(mesh, axis, values,
indexable_getter=..., policy=...)`` — values are any pytree of arrays
(leading axis N, divisible by the shard count) — and queries are REAL
predicate pytrees through the inherited polymorphic ``query()``, exactly
as for BVH/BruteForce. A raw (N, dim) coordinate array is adapted to
``Points`` by the access traits when the default getter is used.

Structure (mirrors the paper):
  * each shard builds a LOCAL search index (LBVH) over its block of
    values' bounding boxes;
  * a TOP index of per-shard scene bounds is replicated everywhere (R
    boxes, R = shard count — a linear scan over R boxes plays the role of
    ArborX's top tree, exact for the R <= 64 meshes we target);
  * queries originate on their owning shard, travel to shards whose top
    box they may touch (all-gather of the predicate batch — the
    roundtrip-minimal pattern for dense query sets), are answered against
    local data, and the per-shard partial results return to the
    originating shard via ``all_to_all``;
  * CALLBACKS RUN ON THE DATA-OWNING SHARD (§2.3's headline feature): only
    the reduced callback state crosses the interconnect, never the stored
    values. Correspondingly ``QueryResult.values`` is None here by
    default — reduce data-side with ``callback=`` instead of shipping
    values. Attach-data scenarios that DO need the matched values opt in
    with ``policy.override(ship_values=True)``; the collective then moves
    exactly the matched rows.

All paths are jit/shard_map-closed: shapes are static, results land
sharded over the same axis as the originating predicates (whose batch
length must divide evenly by the shard count).

Not supported distributed: ``RayOrderedIntersect`` (its collect state
cannot psum across shards), flavor-2 output queries (values stay remote),
and ``Nearest.exclude`` — all raise ``NotImplementedError``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as PS

from repro.compat import shard_map

from . import callbacks as CB
from . import geometry as G
from . import predicates as Pred
from . import traversal as T
from .access import as_geometry, default_indexable_getter
from .index import ExecutionPolicy, Index, _bcast_state, _warn_deprecated
from .lbvh import build as lbvh_build

__all__ = ["DistributedTree", "ship_values_baseline"]


def _all_gather_tree(pytree, axis):
    return jax.tree_util.tree_map(
        lambda a: jax.lax.all_gather(a, axis, tiled=True), pytree)


class DistributedTree(Index):
    """Distributed BVH over values sharded along ``axis`` of ``mesh``.

    values: pytree of arrays with leading axis N; N must divide evenly by
    the shard count, with at least 2 values per shard.
    """

    def __init__(self, mesh, axis: str, values,
                 indexable_getter=default_indexable_getter, *,
                 policy: ExecutionPolicy | None = None):
        self._init_meta(mesh, axis, values, indexable_getter, policy)

        def build_local(vals_local):
            tree = lbvh_build(indexable_getter(vals_local))
            return tree, (tree.node_lo[:1], tree.node_hi[:1])

        spec = PS(axis)
        built = jax.jit(shard_map(
            build_local, mesh=mesh, in_specs=(spec,),
            out_specs=(spec, (spec, spec)), check_vma=False))(self.values)
        self.trees, (self.top_lo, self.top_hi) = built
        # self.trees: pytree whose arrays are shard-concatenated local trees
        # self.top_lo/hi: (R, dim) replicated-by-construction top boxes

    @staticmethod
    def _adapt_values(values, indexable_getter):
        if (indexable_getter is default_indexable_getter
                and isinstance(values, (jax.Array, np.ndarray))):
            # adapt raw (N, dim) coordinate arrays through the access traits
            # so leaf tests see a geometry container
            return as_geometry(jnp.asarray(values))
        return values

    def _init_meta(self, mesh, axis, values, indexable_getter, policy):
        if axis not in mesh.shape:
            raise ValueError(f"axis {axis!r} is not an axis of the mesh "
                             f"(axes: {tuple(mesh.axis_names)})")
        self.mesh = mesh
        self.axis = axis
        self.policy = policy or ExecutionPolicy()
        values = self._adapt_values(values, indexable_getter)
        self.values = values
        self._getter = indexable_getter
        boxes = indexable_getter(values)
        self.R = mesh.shape[axis]
        n = len(boxes)
        self.dim = boxes.dim
        if n % self.R:
            raise ValueError(f"N={n} not divisible by shard count {self.R}")
        self.n_local = n // self.R
        if self.n_local < 2:
            raise ValueError(
                f"DistributedTree needs >= 2 values per shard (got N={n} "
                f"over {self.R} shards); use BVH for degenerate sizes")

    @classmethod
    def from_local_trees(cls, mesh, axis: str, values, trees, top_lo, top_hi,
                         indexable_getter=default_indexable_getter, *,
                         policy: ExecutionPolicy | None = None):
        """Wrap PREBUILT per-shard local trees — the swap-in constructor
        for distributed refit (``ShardedIndexStore``): no re-sort, no
        rebuild, no re-gather of the top index.

        ``trees`` must be the shard-concatenated LBVH pytree produced under
        the SAME ``(mesh, axis)`` over these values (what ``__init__`` or a
        per-shard ``shard_map`` refit yields); ``top_lo``/``top_hi`` are the
        (R, dim) per-shard scene bounds. Mismatched mesh/axis/leaf-count
        raise a loud ``ValueError`` rather than serving a torn index.
        """
        obj = cls.__new__(cls)
        obj._init_meta(mesh, axis, values, indexable_getter, policy)
        n = obj.R * obj.n_local
        n_leaves = int(trees.leaf_perm.shape[0])
        if n_leaves != n:
            raise ValueError(
                f"local trees cover {n_leaves} leaves but values have N={n};"
                " rebuild instead of wrapping stale trees")
        want_nodes = 2 * n - obj.R     # R shards x (2*n_local - 1) nodes
        got_nodes = int(trees.node_lo.shape[0])
        if got_nodes != want_nodes:
            raise ValueError(
                f"local trees hold {got_nodes} nodes but a {obj.R}-shard "
                f"mesh over N={n} values needs {want_nodes} (= 2N - R); "
                "were these trees built under a different mesh/axis?")
        top_lo = jnp.asarray(top_lo)
        top_hi = jnp.asarray(top_hi)
        want_top = (obj.R, obj.dim)
        if top_lo.shape != want_top or top_hi.shape != want_top:
            raise ValueError(
                f"top bounds must be per-shard scene boxes of shape "
                f"{want_top}; got {top_lo.shape} / {top_hi.shape}")
        obj.trees = trees
        obj.top_lo = top_lo
        obj.top_hi = top_hi
        return obj

    # --- container interface ---------------------------------------------
    def size(self) -> int:
        return self.R * self.n_local

    def bounds(self) -> G.Boxes:
        return G.Boxes(jnp.min(self.top_lo, axis=0, keepdims=True),
                       jnp.max(self.top_hi, axis=0, keepdims=True))

    # --- helpers ----------------------------------------------------------
    def _check_q(self, predicates):
        # Q == 0 short-circuits in every hook: XLA forbids zero-length
        # all_gather dims, and there is nothing to communicate anyway
        nq = len(predicates)
        if nq % self.R:
            raise ValueError(f"predicate batch Q={nq} not divisible by "
                             f"shard count {self.R}")
        return nq

    def _shard_call(self, step, *operands, n_out: int):
        spec = PS(self.axis)
        out_specs = spec if n_out == 1 else (spec,) * n_out
        return jax.jit(shard_map(
            step, mesh=self.mesh, in_specs=(spec,) * (2 + len(operands)),
            out_specs=out_specs, check_vma=False))(
                self.trees, self.values, *operands)

    # --- backend SPI ------------------------------------------------------
    def _knn_impl(self, predicates, pol):
        """Nearest / RayNearest: local traversals on every shard, then the
        per-shard candidate lists (only (R*k) scalars per query) return to
        the originating shard and merge by distance / ray parameter."""
        if getattr(predicates, "exclude", None) is not None:
            raise NotImplementedError(
                "Nearest.exclude is not supported on DistributedTree")
        axis, R, n_local = self.axis, self.R, self.n_local
        k = predicates.k
        if self._check_q(predicates) == 0:
            return (jnp.zeros((0, k), jnp.float32),
                    jnp.full((0, k), -1, jnp.int32))

        def step(trees, vals_local, preds_local):
            preds_all = _all_gather_tree(preds_local, axis)
            d, i = T.traverse_knn(trees, vals_local, preds_all, k)
            # globalize indices: shard r holds rows [r*n_local, ...)
            r = jax.lax.axis_index(axis)
            gi = jnp.where(i >= 0, i + r * n_local, -1)
            # return partial results to originating shards
            qloc = len(preds_all) // R
            d = jax.lax.all_to_all(d.reshape(R, qloc, k), axis, 0, 0)
            gi = jax.lax.all_to_all(gi.reshape(R, qloc, k), axis, 0, 0)
            # merge R candidate lists per query (callbacks stayed data-side)
            d = jnp.moveaxis(d, 0, 1).reshape(qloc, R * k)
            gi = jnp.moveaxis(gi, 0, 1).reshape(qloc, R * k)
            order = jnp.argsort(d, axis=1)[:, :k]
            return (jnp.take_along_axis(d, order, 1),
                    jnp.take_along_axis(gi, order, 1))

        return self._shard_call(step, predicates, n_out=2)

    def _query_callback_impl(self, predicates, callback, state0, pol):
        """Distributed pure-callback query (§2.3: callbacks execute on the
        shard OWNING the data; only reduced states cross the network).

        ``pol.combine`` is the monoid combining per-shard states; the
        default (None) is an elementwise psum, correct for arithmetic
        states whose initial value is zero. Non-psum combines must be
        idempotent in state0 (it seeds every shard)."""
        axis, R = self.axis, self.R
        combine = pol.combine
        if self._check_q(predicates) == 0:
            return state0        # already batched to (0, ...)

        def step(trees, vals_local, preds_local, s0_local):
            preds_all = _all_gather_tree(preds_local, axis)
            s0_all = _all_gather_tree(s0_local, axis)
            states = T.traverse(trees, vals_local, preds_all, callback, s0_all)
            if combine is None:
                states = jax.tree_util.tree_map(
                    lambda a: jax.lax.psum(a, axis), states)
            else:
                gathered = jax.tree_util.tree_map(
                    lambda a: jax.lax.all_gather(a, axis), states)  # (R, Q, .)
                acc = jax.tree_util.tree_map(lambda a: a[0], gathered)
                for r in range(1, R):
                    acc = combine(acc, jax.tree_util.tree_map(
                        lambda a: a[r], gathered))
                states = acc
            # each shard keeps its own queries' slice
            r = jax.lax.axis_index(axis)
            qloc = len(preds_all) // R
            return jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_slice_in_dim(a, r * qloc, qloc),
                states)

        return self._shard_call(step, predicates, state0, n_out=1)

    def _count_impl(self, predicates, pol):
        cb, s0 = CB.counting()
        # counting MUST psum across shards: force combine back to the
        # default even when the bound policy carries a custom monoid
        # (override() drops None kwargs, so spell it with replace)
        return self._query_callback_impl(
            predicates, cb, _bcast_state(s0, len(predicates)),
            dataclasses.replace(pol, combine=None))

    def _fill_impl(self, predicates, capacity, pol):
        """CSR fill: every shard collects up to `capacity` local matches,
        the (R, capacity) index buffers return to the originating shard and
        pack valid-first. Counts are FULL global counts, so the base
        class's doubling retry guarantees no shard clamps locally once the
        retry capacity covers the global maximum."""
        axis, R, n_local = self.axis, self.R, self.n_local
        if self._check_q(predicates) == 0:
            return (jnp.zeros((0,), jnp.int32),
                    jnp.full((0, capacity), -1, jnp.int32))

        def step(trees, vals_local, preds_local):
            preds_all = _all_gather_tree(preds_local, axis)
            nq = len(preds_all)
            cb, s0 = CB.collect_hits(capacity)
            s0 = _bcast_state(s0, nq)
            count, idxs, _ = T.traverse(trees, vals_local, preds_all, cb, s0)
            r = jax.lax.axis_index(axis)
            gi = jnp.where(idxs >= 0, idxs + r * n_local, -1)
            qloc = nq // R
            count = jax.lax.all_to_all(count.reshape(R, qloc), axis, 0, 0)
            gi = jax.lax.all_to_all(gi.reshape(R, qloc, capacity), axis, 0, 0)
            gi = jnp.moveaxis(gi, 0, 1).reshape(qloc, R * capacity)
            # valid-first stable pack, then clamp to the caller's capacity
            order = jnp.argsort((gi < 0).astype(jnp.int32), axis=1,
                                stable=True)
            buf = jnp.take_along_axis(gi, order, 1)[:, :capacity]
            return jnp.moveaxis(count, 0, 1).sum(1).astype(jnp.int32), buf

        return self._shard_call(step, predicates, n_out=2)

    def _collect_with_t(self, predicates, capacity, pol):
        raise NotImplementedError(
            "RayOrderedIntersect is single-node only (the collect state "
            "cannot cross shards); gather values locally or use RayNearest")

    def _gather_values(self, flat_idx, pol=None):
        """Values live on their owning shard; by default results carry
        global indices only (``QueryResult.values is None`` — reduce
        data-side with ``callback=``, §2.3). ``policy.ship_values=True``
        opts in for attach-data scenarios: each shard contributes the
        matched rows it owns and one psum delivers them everywhere, so
        collective bytes scale with matches × value size — the
        generalization of the retired :func:`ship_values_baseline` (any
        values pytree, any predicate kind, exactly the matched set)."""
        if pol is None or not pol.ship_values:
            return None
        if int(flat_idx.shape[0]) == 0:
            # nothing matched: no collective (XLA also rejects zero-length
            # all_gather dims); a plain local gather yields the empty pytree
            return T.value_at(self.values, flat_idx)
        axis, n_local = self.axis, self.n_local

        def step(vals_local, idx):
            r = jax.lax.axis_index(axis)
            local = idx - r * n_local
            mine = (local >= 0) & (local < n_local)
            li = jnp.clip(local, 0, n_local - 1)

            def pick(a):
                v = a[li]
                mask = mine.reshape((-1,) + (1,) * (v.ndim - 1))
                return jax.lax.psum(jnp.where(mask, v, jnp.zeros((), v.dtype)),
                                    axis)

            return jax.tree_util.tree_map(pick, vals_local)

        return jax.jit(shard_map(
            step, mesh=self.mesh, in_specs=(PS(self.axis), PS()),
            out_specs=PS(), check_vma=False))(self.values, flat_idx)

    # --- deprecation shims (the old per-kind methods) ---------------------
    def query_knn(self, queries, k: int):
        """DEPRECATED: use ``query(nearest(Points(queries), k))``."""
        _warn_deprecated(
            "DistributedTree.query_knn", "query_knn(queries, k) is "
            "deprecated; use query(nearest(G.Points(queries), k=k)) and "
            "read .distances/.indices")
        res = self.query(Pred.nearest(G.Points(queries), k=k))
        return res.distances, res.indices

    def query_radius_count(self, queries, radius):
        """DEPRECATED: use ``query(intersects(Spheres(...)),
        callback=callbacks.counting())`` (or ``count``)."""
        _warn_deprecated(
            "DistributedTree.query_radius_count", "query_radius_count is "
            "deprecated; use count(intersects(G.Spheres(centers, radii)))")
        nq = queries.shape[0]
        return self.count(Pred.intersects(G.Spheres(
            queries, jnp.full((nq,), radius, queries.dtype))))

    def query_ray_nearest(self, origins, directions, k: int = 1):
        """DEPRECATED: use ``query(RayNearest(Rays(o, d), k))``."""
        _warn_deprecated(
            "DistributedTree.query_ray_nearest", "query_ray_nearest is "
            "deprecated; use query(P.RayNearest(G.Rays(o, d), k))")
        res = self.query(Pred.RayNearest(G.Rays(origins, directions), k))
        return res.distances, res.indices

    def query_callback(self, predicates_maker, callback, state0, queries,
                       combine=None):
        """DEPRECATED: use ``query(predicates, callback=(cb, state0),
        policy=policy.override(combine=...))`` with a real predicate
        batch."""
        _warn_deprecated(
            "DistributedTree.query_callback", "query_callback(maker, cb, "
            "state0, queries) is deprecated; build the predicate batch "
            "yourself and call query(predicates, callback=(cb, state0))")
        preds = predicates_maker(queries)
        return self.query(preds, callback=(callback, state0),
                          policy=self.policy.override(combine=combine))

    def query_values_to_origin(self, queries, radius, capacity: int):
        """DEPRECATED alias of :func:`ship_values_baseline`."""
        _warn_deprecated(
            "DistributedTree.query_values_to_origin", "query_values_to_"
            "origin is deprecated; use query(predicates, policy=policy."
            "override(ship_values=True)) to ship matched values")
        return ship_values_baseline(self, queries, radius, capacity)


def ship_values_baseline(tree: DistributedTree, queries, radius,
                         capacity: int):
    """DEPRECATED anti-pattern baseline for the §2.3 benchmark: ship up to
    `capacity` matched VALUES (coordinates) back to the originating shard
    instead of reducing data-side. Collective bytes scale with capacity *
    dim — compare with the counting callback in the HLO. Requires Points
    values. New code wants ``query(preds,
    policy=tree.policy.override(ship_values=True))``, which ships exactly
    the matched set for any values pytree and any predicate kind."""
    _warn_deprecated(
        "ship_values_baseline", "ship_values_baseline is deprecated; use "
        "query(predicates, policy=policy.override(ship_values=True)) — "
        "QueryResult.values then carries the matched values")
    if not isinstance(tree.values, G.Points):
        raise TypeError("ship_values_baseline requires Points values")
    axis, R, n_local = tree.axis, tree.R, tree.n_local

    def step(trees, vals_local, q_local):
        q_all = jax.lax.all_gather(q_local, axis, tiled=True)
        nq = q_all.shape[0]
        preds = Pred.intersects(G.Spheres(
            q_all, jnp.full((nq,), radius, q_all.dtype)))
        cb, s0 = CB.collect_hits(capacity)
        s0 = _bcast_state(s0, nq)
        count, idxs, _ = T.traverse(trees, vals_local, preds, cb, s0)
        coords_local = vals_local.coords
        vals = coords_local[jnp.maximum(idxs, 0)]          # (Q, cap, dim)
        vals = jnp.where((idxs >= 0)[..., None], vals, jnp.inf)
        qloc = q_local.shape[0]
        vals = jax.lax.all_to_all(
            vals.reshape(R, qloc, capacity, vals.shape[-1]), axis, 0, 0)
        count = jax.lax.all_to_all(count.reshape(R, qloc), axis, 0, 0)
        return jnp.moveaxis(vals, 0, 1), jnp.moveaxis(count, 0, 1)

    spec = PS(axis)
    return jax.jit(shard_map(
        step, mesh=tree.mesh, in_specs=(spec, spec, spec),
        out_specs=(spec, spec), check_vma=False))(
            tree.trees, tree.values, queries)

"""The unified ``Index`` protocol (ArborX 2.0 §2.1–2.2).

The headline of API v2 is that every search structure stores user *values*
with an *indexable getter* and answers ONE generic query call, regardless
of backend. The three structures here — :class:`~repro.core.bvh.BVH`,
:class:`~repro.core.brute_force.BruteForce`, and
:class:`~repro.core.distributed.DistributedTree` — all derive from
:class:`Index`:

    index = BVH(values, indexable_getter=..., policy=ExecutionPolicy(...))
    result = index.query(predicates)            # dispatch on predicate kind

Predicate dispatch (the one ``query``):

    ==================== =============================================
    predicate kind        result (a :class:`QueryResult`)
    ==================== =============================================
    Intersects            CSR spatial join: values/indices/offsets
    Nearest               dense kNN: distances/indices (Q, k)
    RayNearest            dense first-k hits: distances (= t)/indices
    RayIntersect          CSR all-hits: values/indices/offsets
    RayOrderedIntersect   CSR sorted by t: indices/offsets/distances
    ==================== =============================================

    query(preds, callback=(cb, state0))   # flavor 1: pure callback, the
                                          # per-query reduced states return
    query(preds, out=out_fn)              # flavor 2: callback output, CSR

The per-call execution-space argument of API v1 is gone: engine selection,
device placement, and the capacity/overflow strategy live in an
:class:`ExecutionPolicy` bound at construction and overridable per call
via ``policy=`` (or the ``capacity=`` shorthand).

Backends implement a small SPI; everything CSR-shaped (two-pass count →
fill, capacity-doubling overflow retries, the ordered-ray segment sort,
flavor-2 output packing) lives HERE, once, so all backends share the same
result-layout semantics:

    _query_callback_impl(preds, cb, state0_batched, policy) -> states
    _count_impl(preds, policy)            -> (Q,) int32 full counts
    _fill_impl(preds, capacity, policy)   -> (counts, idx_buf (Q, cap))
    _knn_impl(preds, policy)              -> (dists, idxs) (Q, k)
    _csr_exact(preds, policy)             -> QueryResult | None (fast path)
    _collect_with_t(preds, cap, policy)   -> (counts, idxs, ts)
    _gather_values(flat_idx, policy)      -> values pytree | None

Legacy spellings (``query(space, preds)``, ``count(space, preds)``,
``knn``, ``query_callback``, ``query_out``, and the DistributedTree
``query_knn``-style methods) survive as thin deprecation shims that warn
once per spelling; ``scripts/tier1.sh`` runs the suite under
``-W error::DeprecationWarning`` so no in-repo call site can linger.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from . import callbacks as CB
from . import predicates as P

__all__ = ["ExecutionPolicy", "Index", "QueryResult"]


class QueryResult(NamedTuple):
    """The typed result of :meth:`Index.query` (a real tuple: unpacks
    positionally and is a pytree, so it passes through jit/vmap).

    Which fields are populated depends on the predicate kind (see the
    dispatch table in the module docstring); absent fields are None.

    values:    matched values (CSR flat for spatial, (Q, k, ...) for kNN);
               None on DistributedTree (values stay on the owning shard —
               use callbacks to reduce data-side, §2.3).
    indices:   matched original indices — CSR flat or (Q, k) (-1 padded).
    offsets:   (Q+1,) CSR row offsets for spatial/ray-intersect results.
    distances: fine distances for kNN, ray parameter t for ray results.
    overflow:  True when a caller-supplied capacity was exceeded even
               after the doubling retries (CSR result is truncated).
    """
    values: Any = None
    indices: Any = None
    offsets: Any = None
    distances: Any = None
    overflow: bool = False


@dataclasses.dataclass(frozen=True)
class ExecutionPolicy:
    """Execution parameters bound to an index at construction (ArborX's
    execution-space argument, made explicit) and overridable per call via
    ``query(..., policy=...)``.

    engine:        QueryEngine doing route selection (bruteforce / loop);
                   None -> the process default engine.
    device:        jax.Device the index (tree + values) is placed on at
                   build; None -> default device. Queries run where the
                   arrays live (XLA's async dispatch replaces per-call
                   execution-space instances).
    capacity:      default CSR buffer width per query for storage queries;
                   None -> exact two-pass sizing (count, then fill).
    max_doublings: how many capacity-doubling fill retries a storage query
                   may take before flagging ``overflow`` (0 pins the raw
                   truncation contract).
    combine:       distributed-only: monoid combining per-shard callback
                   states (default None -> elementwise psum, correct for
                   zero-initialized arithmetic states). Ignored by
                   single-process backends.
    build_engine:  LBVH construction path: "pallas" (fused build kernels)
                   | "ref" (reference jit pipeline) | "auto"/None (the
                   fused build — both are bit-identical).
    ship_values:   distributed-only: opt in to shipping MATCHED values to
                   the originating shard so ``QueryResult.values`` is
                   populated (attach-data scenarios). Off by default —
                   the §2.3 design reduces data-side via callbacks; when
                   on, collective bytes scale with matches × value size.
                   Single-process backends always gather locally and
                   ignore this flag.
    """
    engine: Any = None
    device: Any = None
    capacity: int | None = None
    max_doublings: int = 6
    combine: Any = None
    build_engine: str | None = None
    ship_values: bool = False

    def __post_init__(self):
        if self.build_engine is not None and \
                self.build_engine not in ("auto", "pallas", "ref"):
            raise ValueError(f"build_engine={self.build_engine!r} is not "
                             "one of ('auto', 'pallas', 'ref')")

    def resolve_engine(self):
        if self.engine is not None:
            return self.engine
        from . import engine as E
        return E.default_engine()

    def override(self, **kw) -> "ExecutionPolicy":
        """Copy with the given non-None fields replaced."""
        kw = {k: v for k, v in kw.items() if v is not None}
        return dataclasses.replace(self, **kw) if kw else self


# --- deprecation shims -----------------------------------------------------

_SEEN_DEPRECATIONS: set = set()


def _warn_deprecated(key: str, msg: str):
    if key in _SEEN_DEPRECATIONS:
        return
    _SEEN_DEPRECATIONS.add(key)
    warnings.warn(msg, DeprecationWarning, stacklevel=3)


class _LegacyTriple(tuple):
    """Old storage-query result: a (values, indices, offsets) 3-tuple with
    an ``overflow`` attribute. Returned only by the deprecated
    ``query(space, predicates)`` spelling."""

    def __new__(cls, triple, overflow: bool = False):
        obj = super().__new__(cls, triple)
        obj.overflow = overflow
        return obj


def _bcast_state(state, nq):
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(jnp.asarray(a), (nq,) + jnp.shape(jnp.asarray(a))),
        state)


def _replicated(a):
    """`a` with its rows no longer split over an explicit mesh axis.

    A ``shard_map`` over an ``Explicit`` mesh (jax 0.9's ``make_mesh``
    default) returns arrays whose type names the axis; jax refuses to
    scatter such rows into an unsharded buffer, so they are replicated
    over the same mesh first. Other arrays pass through."""
    s = a.sharding
    if isinstance(s, jax.sharding.NamedSharding) and any(s.spec):
        return jax.device_put(a, s.update(spec=jax.sharding.PartitionSpec()))
    return a


def _csr_pack(buf, counts, offsets, total):
    """(Q, cap) buffer + per-query counts -> flat (total,) CSR array."""
    buf, counts, offsets = map(_replicated, (buf, counts, offsets))
    q, cap = buf.shape
    ar = jnp.arange(cap)[None, :]
    valid = ar < counts[:, None]
    pos = offsets[:-1][:, None] + ar
    flat = jnp.zeros((total + 1,), buf.dtype)
    flat = flat.at[jnp.where(valid, pos, total)].set(buf)
    return flat[:total]


def _repeat_preds(predicates, offsets, total):
    """Expand per-query predicates to per-match (CSR repeat)."""
    counts = offsets[1:] - offsets[:-1]
    qid = jnp.repeat(jnp.arange(counts.shape[0]), counts, total_repeat_length=total)
    return jax.tree_util.tree_map(lambda a: a[qid], predicates)


class Index:
    """Base class: the unified container + query interface (§2.1.3).

    Subclasses set ``self.policy`` (an :class:`ExecutionPolicy`) during
    construction and implement the backend SPI (see module docstring).
    """

    policy: ExecutionPolicy

    # --- container interface ---------------------------------------------
    def size(self) -> int:
        raise NotImplementedError

    def empty(self) -> bool:
        return self.size() == 0

    def bounds(self):
        raise NotImplementedError

    # --- THE query -------------------------------------------------------
    def query(self, predicates, *_legacy, callback=None, out=None,
              capacity: int | None = None, policy: ExecutionPolicy | None = None):
        """One polymorphic query: dispatches on the predicate kind (see the
        module docstring's table) and returns a :class:`QueryResult`,
        except for the ``callback=`` flavor which returns the per-query
        final states.

        callback: ``(cb, state0)`` pair — the traversal callback protocol
            ``cb(state, pred, value, index, t) -> (new_state, done)`` with
            an UNBATCHED initial state (broadcast to every query).
            Exactly what the :mod:`repro.core.callbacks` factories return.
        out:      ``out_fn(pred, value, index, t) -> output element``; the
            per-match outputs are stored CSR in ``result.values``
            (§2.1.3 flavor 2 — the output type may differ from Value).
        capacity: per-query CSR width shorthand (== policy.capacity).
        policy:   full per-call ExecutionPolicy override.
        """
        if _legacy:
            return self._legacy_query(predicates, *_legacy, callback=callback,
                                      out=out, capacity=capacity, policy=policy)
        pol = policy if policy is not None else self.policy
        if capacity is not None:
            pol = pol.override(capacity=capacity)

        if callback is not None:
            cb, state0 = callback
            s0 = _bcast_state(state0, len(predicates))
            return self._query_callback_impl(predicates, cb, s0, pol)
        if out is not None:
            return self._query_out(predicates, out, pol)
        if isinstance(predicates, (P.Nearest, P.RayNearest)):
            return self._query_knn(predicates, pol)
        if isinstance(predicates, P.RayOrderedIntersect):
            return self._query_ordered(predicates, pol)
        if isinstance(predicates, (P.Intersects, P.RayIntersect)):
            return self._query_csr(predicates, pol)
        raise TypeError(f"query() cannot dispatch predicate kind "
                        f"{type(predicates).__name__}")

    def count(self, predicates, *_legacy, policy: ExecutionPolicy | None = None):
        """Per-query match counts for Intersects/ray predicates — the
        cheap companion to the storage query (no fill pass)."""
        if _legacy:
            _warn_deprecated(
                "count", "count(space, predicates) is deprecated; the "
                "execution space lives in ExecutionPolicy now — call "
                "count(predicates)")
            predicates = _legacy[0]
        pol = policy if policy is not None else self.policy
        return self._count_impl(predicates, pol)

    # --- dispatch bodies (shared across ALL backends) ---------------------
    def _query_knn(self, predicates, pol) -> QueryResult:
        d, i = self._knn_impl(predicates, pol)
        if self.size() == 0:        # nothing to gather values from
            return QueryResult(indices=i, distances=d)
        vals = self._gather_values(jnp.maximum(i, 0).reshape(-1), pol)
        if vals is not None:
            q, k = i.shape
            vals = jax.tree_util.tree_map(
                lambda a: a.reshape((q, k) + a.shape[1:]), vals)
        return QueryResult(values=vals, indices=i, distances=d)

    def _query_csr(self, predicates, pol) -> QueryResult:
        nq = len(predicates)
        overflow = False
        cap = pol.capacity
        if cap is None:
            exact = self._csr_exact(predicates, pol)
            if exact is not None:
                return exact
            counts = self._count_impl(predicates, pol)
            cap = max(int(counts.max()), 1) if nq else 1
            counts, buf = self._fill_impl(predicates, cap, pol)
        else:
            counts, buf = self._fill_impl(predicates, cap, pol)
            # counts are FULL counts (the fill pass only clamps the
            # buffer), so one host sync decides the retry width outright
            needed = int(counts.max()) if nq else 0
            if needed > cap:
                retry = cap
                for _ in range(pol.max_doublings):
                    if retry >= needed:
                        break
                    retry *= 2
                if retry > cap:
                    counts, buf = self._fill_impl(predicates, retry, pol)
                    cap = retry
                overflow = needed > cap
        clamped = jnp.minimum(counts, cap)
        offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                   jnp.cumsum(clamped)]).astype(jnp.int32)
        total = int(offsets[-1])
        flat_idx = _csr_pack(buf, clamped, offsets, total)
        return QueryResult(values=self._gather_values(flat_idx, pol),
                           indices=flat_idx, offsets=offsets,
                           overflow=overflow)

    def _query_ordered(self, predicates, pol) -> QueryResult:
        """All ray hits ordered by t within each ray (§2.5): collect +
        per-ray segment sort — the TPU-friendly spelling of ordered
        traversal (a data-dependent in-order walk is serial; collect+sort
        is two vector passes)."""
        nq = len(predicates)
        cap = pol.capacity
        if cap is None:
            # jnp.max of an empty counts array would throw
            cap = max(int(self._count_impl(predicates, pol).max()), 1) if nq else 1
        count, idxs, ts = self._collect_with_t(predicates, cap, pol)
        count = jnp.minimum(count, cap)
        # invalid slots already hold t=inf, so a plain per-row sort pushes
        # them past the valid segment
        order = jnp.argsort(ts, axis=1)
        ts_s = jnp.take_along_axis(ts, order, axis=1)
        idxs_s = jnp.take_along_axis(idxs, order, axis=1)
        offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                   jnp.cumsum(count)]).astype(jnp.int32)
        total = int(offsets[-1])
        flat_idx = _csr_pack(idxs_s, count, offsets, total)
        flat_t = _csr_pack(ts_s, count, offsets, total)
        return QueryResult(values=self._gather_values(flat_idx, pol),
                           indices=flat_idx, offsets=offsets,
                           distances=flat_t)

    def _query_out(self, predicates, out_fn, pol) -> QueryResult:
        res = self._query_csr(predicates, pol)
        if res.values is None:
            raise NotImplementedError(
                f"{type(self).__name__} cannot run output queries: matched "
                "values stay on the owning shard (use callback=)")
        preds_rep = _repeat_preds(predicates, res.offsets, res.indices.shape[0])
        # per-match t is recomputed for ray predicates during packing when
        # needed — spatial callbacks receive t=0
        t = jnp.zeros((res.indices.shape[0],), jnp.float32)
        out = jax.vmap(out_fn)(preds_rep, res.values, res.indices, t)
        return QueryResult(values=out, indices=res.indices,
                           offsets=res.offsets, overflow=res.overflow)

    # --- backend SPI ------------------------------------------------------
    def _query_callback_impl(self, predicates, callback, state0, pol):
        raise NotImplementedError

    def _count_impl(self, predicates, pol):
        raise NotImplementedError

    def _fill_impl(self, predicates, capacity, pol):
        raise NotImplementedError

    def _knn_impl(self, predicates, pol):
        raise NotImplementedError

    def _csr_exact(self, predicates, pol):
        return None

    def _collect_with_t(self, predicates, capacity, pol):
        """Default: a collect_hits callback pass (works wherever callback
        states need not cross shard boundaries)."""
        cb, s0 = CB.collect_hits(capacity)
        s0 = _bcast_state(s0, len(predicates))
        return self._query_callback_impl(predicates, cb, s0, pol)

    def _gather_values(self, flat_idx, pol=None):
        from .traversal import value_at
        return value_at(self.values, flat_idx)

    # --- deprecation shims (API v1 spellings) -----------------------------
    def _legacy_query(self, space, predicates, *rest, callback=None, out=None,
                      capacity=None, policy=None):
        _warn_deprecated(
            "query", "query(space, predicates, ...) is deprecated; the "
            "execution space lives in ExecutionPolicy now — call "
            "query(predicates, ...) (returns a QueryResult NamedTuple)")
        if rest:
            capacity = rest[0]
        if callback is not None:
            cb, s0 = callback
            s0 = _bcast_state(s0, len(predicates))
            return self._query_callback_impl(
                predicates, cb, s0, policy or self.policy)
        res = self.query(predicates, out=out, capacity=capacity, policy=policy)
        if out is not None:
            return res.values, res.offsets
        return _LegacyTriple((res.values, res.indices, res.offsets),
                             res.overflow)

    def query_callback(self, space, predicates, callback, init_state):
        """DEPRECATED: use ``query(predicates, callback=(cb, state0))``
        (state0 unbatched; this shim keeps the old batched contract)."""
        _warn_deprecated(
            "query_callback", "query_callback(space, preds, cb, state) is "
            "deprecated; use query(predicates, callback=(cb, state0)) with "
            "an unbatched state0")
        return self._query_callback_impl(predicates, callback, init_state,
                                         self.policy)

    def query_out(self, space, predicates, out_fn, capacity: int | None = None):
        """DEPRECATED: use ``query(predicates, out=out_fn)``."""
        _warn_deprecated(
            "query_out", "query_out(space, preds, out_fn) is deprecated; "
            "use query(predicates, out=out_fn)")
        res = self.query(predicates, out=out_fn, capacity=capacity)
        return res.values, res.offsets

    def knn(self, space, predicates):
        """DEPRECATED: use ``query(nearest(geom, k))`` — returns a
        QueryResult with .distances/.indices."""
        _warn_deprecated(
            "knn", "knn(space, predicates) is deprecated; use "
            "query(nearest(geom, k)) and read .distances/.indices")
        return self._knn_impl(predicates, self.policy)

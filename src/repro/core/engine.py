"""QueryEngine: per-batch dispatch between the two query execution paths
(DESIGN.md §3).

ArborX 2.0's headline is that the *same* query API is served by different
index structures whose crossover depends on hardware (brute force wins for
small N / fat queries; the BVH wins asymptotically). Here one batched query
runs on one of two routes:

  * ``bruteforce`` — the MXU path: all-pairs leaf tests / distance matrix
    (``BruteForce``). Exact by construction; fastest while N·Q is small
    because a (Q, N) panel is one matmul-shaped pass.
  * ``loop``       — the vmapped ``lax.while_loop`` traversal
    (``core.traversal``): fully general (any predicate kind, any value
    geometry, arbitrary callbacks).

The rule is one comparison on every backend: a batch whose predicate kind
brute force can answer takes it while N·Q <= :data:`BF_MAX_WORK`, and the
loop otherwise. Routing is static (Python-level: N, Q, predicate type), so
it never traces into jit.
"""
from __future__ import annotations

import collections
import dataclasses
import threading

import jax
import jax.numpy as jnp

from ..telemetry import tracer as TEL
from . import lbvh
from . import predicates as P

__all__ = ["EngineConfig", "EngineStats", "EngineStatsSnapshot", "ExecInfo",
           "QueryEngine", "default_engine", "set_default_engine",
           "ROUTE_BRUTEFORCE", "ROUTE_LOOP", "BF_MAX_WORK"]

ROUTE_BRUTEFORCE = "bruteforce"
ROUTE_LOOP = "loop"

#: largest N·Q a batch answers by brute force: a 1024-query batch up to
#: 4096 points. It is the crossover the TPU runs at, and every backend
#: follows it, so CPU tests take the routes the chip takes. No benchmark
#: cell measures the crossover yet (both sit far past it, on the loop)
BF_MAX_WORK = 1 << 22


@dataclasses.dataclass
class EngineConfig:
    """Engine-level knobs.

    force: route every eligible query to one path ("bruteforce" |
        "loop"); a predicate kind brute force cannot express falls back
        to the rule.
    max_executables: LRU bound on the exec_* executable cache — a long-
        lived service whose leaf count changes across rebuilds must not
        pin one compiled executable per historical N forever.
    """
    force: str | None = None
    max_executables: int = 256

    def __post_init__(self):
        routes = (ROUTE_BRUTEFORCE, ROUTE_LOOP)
        if self.force is not None and self.force not in routes:
            raise ValueError(f"force={self.force!r} is not one of {routes}")


@dataclasses.dataclass(frozen=True)
class EngineStatsSnapshot:
    """Immutable point-in-time copy of :class:`EngineStats`."""
    cache_hits: int = 0
    cache_misses: int = 0
    jit_traces: int = 0

    def snapshot(self) -> "EngineStatsSnapshot":
        return self


def _counter_prop(field: str, doc: str) -> property:
    """Registry-backed compatibility field: reads go to the counter's
    value, writes (the legacy ``stats.x += 1`` spelling, always under the
    caller's stats lock) go to ``Counter.set``."""
    def _get(self):
        return self._counters[field].value

    def _set(self, v):
        self._counters[field].set(v)

    return property(_get, _set, doc=doc)


class EngineStats:
    """Executable-cache accounting (DESIGN.md §5, §10).

    cache_hits/misses count lookups of the per-(route, op, bucket shape)
    executable cache; jit_traces counts ACTUAL retraces — each cached body
    bumps it from inside the traced Python, so it moves only when XLA
    recompiles. A warm service shows hits growing and misses/traces flat.

    Since ISSUE 9 the fields are views over counters in a per-instance
    telemetry :class:`~repro.telemetry.MetricsRegistry` (``.registry``),
    so the same numbers flow into the JSONL metrics export. Field reads
    and writes keep their old meaning; constructing with field keyword
    arguments still seeds the counters but warns once (DeprecationWarning
    — the values now also land in the registry).
    """

    _FIELDS = ("cache_hits", "cache_misses", "jit_traces")

    cache_hits = _counter_prop("cache_hits", "executable-cache hits")
    cache_misses = _counter_prop("cache_misses", "executable-cache misses")
    jit_traces = _counter_prop("jit_traces", "actual XLA retraces")

    def __init__(self, registry=None, **legacy):
        from ..telemetry import MetricsRegistry
        self.registry = registry if registry is not None else MetricsRegistry()
        self._counters = {f: self.registry.counter(f"engine.{f}")
                          for f in self._FIELDS}
        if legacy:
            unknown = sorted(set(legacy) - set(self._FIELDS))
            if unknown:
                raise TypeError(f"EngineStats got unexpected fields {unknown}")
            from .index import _warn_deprecated
            _warn_deprecated(
                "EngineStats.kwargs",
                "constructing EngineStats with field keyword arguments is "
                "deprecated: the fields are now counters in a telemetry "
                "MetricsRegistry (stats.registry); assign fields or use "
                "registry.counter(...) instead")
            for k, v in legacy.items():
                self._counters[k].set(int(v))

    def snapshot(self) -> EngineStatsSnapshot:
        return EngineStatsSnapshot(
            **{f: self._counters[f].value for f in self._FIELDS})

    def __repr__(self):
        body = ", ".join(f"{f}={self._counters[f].value}"
                         for f in self._FIELDS)
        return f"EngineStats({body})"


@dataclasses.dataclass(frozen=True)
class ExecInfo:
    """Per-dispatch metadata returned by the exec_* entry points.

    kernel_us is the device-fenced duration of the executable call (the
    ``engine.kernel`` telemetry span) — 0.0 when telemetry is disabled,
    because fencing would serialize XLA's async dispatch.

    knn_counts is set by ``exec_knn``'s loop route only: a (2, Q) int32
    array on the device, not awaited, of each query's node records read
    and 1 where its pruning bound was seeded (DESIGN.md §7, kNN loop
    counters); one array, so the copy-back makes one transfer for both.
    """
    route: str
    cache_hit: bool
    kernel_us: float = 0.0
    knn_counts: object = None


class QueryEngine:
    """Dispatches batched BVH queries to bruteforce / loop."""

    #: reprolint lock discipline (analysis/locks.py): the executable cache
    #: and its stats counters are only coherent under _cache_lock — the
    #: serving pipeline hits this engine from scheduler AND maintenance
    #: threads concurrently.
    _REPROLINT_GUARDED_BY = {"_executables": "_cache_lock",
                             "stats": "_cache_lock"}

    def __init__(self, config: EngineConfig | None = None):
        self.config = config or EngineConfig()
        self.stats = EngineStats()
        self._executables: collections.OrderedDict[tuple, object] = \
            collections.OrderedDict()
        self._cache_lock = threading.Lock()

    # -- routing (DESIGN.md §3) ------------------------------------------
    def route_spatial(self, bvh, predicates, capacity: int | None = None) -> str:
        """Route an Intersects batch for count/fill (`capacity` does not
        enter the rule). Ray predicates take the loop."""
        return self._pick(bvh.size(), len(predicates),
                          isinstance(predicates, P.Intersects))

    def route_knn(self, bvh, predicates) -> str:
        return self._pick(bvh.size(), len(predicates),
                          isinstance(predicates, P.Nearest))

    def _pick(self, n: int, q: int, bf_ok: bool) -> str:
        force = self.config.force
        if force == ROUTE_BRUTEFORCE and bf_ok:
            return ROUTE_BRUTEFORCE
        if force == ROUTE_LOOP:
            return ROUTE_LOOP
        if bf_ok and n * q <= BF_MAX_WORK:
            return ROUTE_BRUTEFORCE
        return ROUTE_LOOP

    # -- brute-force fill (index-ordered; sets match traversal order) -----
    def bruteforce_fill(self, brute, predicates, capacity: int):
        return brute._fill_impl(predicates, capacity, brute.policy)

    # -- executable cache (DESIGN.md §5) -----------------------------------
    #
    # The service dispatches every micro-batch through these entry points.
    # Each (route, op, bucket shape) gets its own jitted executable whose
    # only inputs are arrays (tree pytree, values pytree, query arrays) —
    # nothing device-resident is closed over, so a refit/rebuild of the same
    # N reuses the warm executable with the new arrays. The traced bodies
    # bump ``stats.jit_traces`` so tests can assert zero recompiles after
    # warmup.

    def _note_trace(self):
        """Traced bodies call this on every ACTUAL retrace. Tracing happens
        on the first invocation of a cached executable — outside _cached's
        critical section — so taking the lock here cannot deadlock, and the
        counter stays exact under concurrent schedulers (two threads racing
        an unlocked += lose increments)."""
        with self._cache_lock:
            self.stats.jit_traces += 1

    def _launch(self, fn, args, *, route: str, op: str, hit: bool,
                **span_args):
        """Run a cached executable under an ``engine.kernel`` span and
        return (result, kernel_us). With telemetry enabled the span is
        device-fenced (block_until_ready), so kernel_us covers actual
        device execution; disabled, this is one flag check and the
        dispatch stays fully async (kernel_us = 0.0)."""
        sp = TEL.span("engine.kernel", route=route, op=op, cache_hit=hit,
                      **span_args)
        with sp:
            out = sp.fence(fn(*args))
        return out, sp.dur_us

    def _cached(self, key, make):
        # locked: concurrent server threads must not compile the same key
        # twice or lose stats increments (IndexStore promises this level of
        # thread-safety; the cache has to match it)
        with self._cache_lock:
            fn = self._executables.get(key)
            hit = fn is not None
            if hit:
                self.stats.cache_hits += 1
                self._executables.move_to_end(key)
            else:
                self.stats.cache_misses += 1
                fn = self._executables[key] = make()
                while len(self._executables) > self.config.max_executables:
                    self._executables.popitem(last=False)  # LRU eviction
        return fn, hit

    def _shape_key(self, bvh, predicates):
        if bvh.tree is None:
            raise ValueError("engine exec_* paths require an index with "
                             "N >= 2 (degenerate N handled by BVH directly)")
        geom = getattr(predicates, "geom", None)
        geom = geom if geom is not None else predicates.rays
        # the getter is part of the key: bodies close over it, and two
        # same-shaped indexes with different getters must not share one
        return (type(predicates).__name__, type(geom).__name__,
                type(bvh.values).__name__, len(predicates), bvh.size(),
                bvh._boxes.dim, bvh._getter)

    def exec_spatial(self, bvh, predicates, capacity: int):
        """Cached count+fill for an Intersects bucket.

        Returns ((counts, idx_buf), ExecInfo): FULL per-query counts plus the
        first `capacity` matched original indices per query (-1 padded).
        """
        route = self.route_spatial(bvh, predicates, capacity)
        # every value a traced body closes over is named IN the key —
        # reprolint TRC004 pins this (a closed-over value missing from the
        # key would let two different executables share one cache slot)
        nq = len(predicates)
        getter = bvh._getter
        key = (route, "spatial", capacity, nq,
               getter) + self._shape_key(bvh, predicates)

        if route == ROUTE_BRUTEFORCE:
            def make():
                def body(values, preds):
                    self._note_trace()
                    from .brute_force import BruteForce
                    return self.bruteforce_fill(
                        BruteForce(values, getter), preds, capacity)
                return jax.jit(body)

            fn, hit = self._cached(key, make)
            out, kus = self._launch(fn, (bvh.values, predicates),
                                    route=route, op="spatial", hit=hit)
            return out, ExecInfo(route, hit, kus)

        def make():
            def body(tree, values, preds):
                self._note_trace()
                from . import callbacks as CB
                from . import traversal as T
                cb, s0 = CB.collect_hits(capacity)
                s0 = jax.tree_util.tree_map(
                    lambda a: jnp.broadcast_to(a, (nq,) + jnp.shape(a)), s0)
                count, idxs, _ = T.traverse(tree, values, preds, cb, s0)
                return count, idxs
            return jax.jit(body)

        fn, hit = self._cached(key, make)
        out, kus = self._launch(fn, (bvh.tree, bvh.values, predicates),
                                route=ROUTE_LOOP, op="spatial", hit=hit,
                                node_record_lanes=lbvh.node_record_lanes(
                                    bvh.tree.dim))
        return out, ExecInfo(ROUTE_LOOP, hit, kus)

    def exec_knn(self, bvh, predicates):
        """Cached kNN for a Nearest bucket. Returns ((dists, idxs), ExecInfo);
        on the loop route ``ExecInfo.knn_counts`` holds each query's step
        count and whether its pruning bound was seeded."""
        route = self.route_knn(bvh, predicates)
        k = predicates.k
        getter = bvh._getter
        key = (route, "knn", k, getter) + self._shape_key(bvh, predicates)

        if route == ROUTE_BRUTEFORCE:
            def make():
                def body(values, preds):
                    self._note_trace()
                    from .brute_force import BruteForce
                    bf = BruteForce(values, getter)
                    return bf._knn_impl(preds, bf.policy)
                return jax.jit(body)

            fn, hit = self._cached(key, make)
            out, kus = self._launch(fn, (bvh.values, predicates),
                                    route=route, op="knn", hit=hit)
            return out, ExecInfo(route, hit, kus)

        def make():
            def body(tree, values, preds):
                self._note_trace()
                from . import traversal as T
                dists, idxs, steps, seeded = T.traverse_knn(
                    tree, values, preds, k, count_steps=True)
                return dists, idxs, jnp.stack([steps, seeded.astype(
                    steps.dtype)])
            return jax.jit(body)

        fn, hit = self._cached(key, make)
        (dists, idxs, knn_counts), kus = self._launch(
            fn, (bvh.tree, bvh.values, predicates), route=ROUTE_LOOP,
            op="knn", hit=hit,
            node_record_lanes=lbvh.node_record_lanes(bvh.tree.dim))
        return (dists, idxs), ExecInfo(ROUTE_LOOP, hit, kus, knn_counts)

    def exec_ray_nearest(self, bvh, rays, k: int):
        """Cached first-k ray hits (always the general loop path).
        Returns ((t, idx), ExecInfo) with (Q, k) arrays padded (inf, -1)."""
        preds = P.RayNearest(rays, k)
        key = (ROUTE_LOOP, "ray_nearest", k) + self._shape_key(bvh, preds)

        def make():
            def body(tree, values, rays_):
                self._note_trace()
                from . import traversal as T
                return T.traverse_knn(tree, values, P.RayNearest(rays_, k), k)
            return jax.jit(body)

        fn, hit = self._cached(key, make)
        out, kus = self._launch(fn, (bvh.tree, bvh.values, rays),
                                route=ROUTE_LOOP, op="ray_nearest", hit=hit,
                                node_record_lanes=lbvh.node_record_lanes(
                                    bvh.tree.dim))
        return out, ExecInfo(ROUTE_LOOP, hit, kus)


_DEFAULT = QueryEngine()


def default_engine() -> QueryEngine:
    return _DEFAULT


def set_default_engine(engine: QueryEngine):
    global _DEFAULT
    _DEFAULT = engine

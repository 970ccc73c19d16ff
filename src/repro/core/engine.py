"""QueryEngine: per-batch dispatch between the three query execution paths
(DESIGN.md §3).

ArborX 2.0's headline is that the *same* query API is served by different
index structures whose crossover depends on hardware (brute force wins for
small N / fat queries; the BVH wins asymptotically). On TPU there are three
distinct engines for one batched query:

  * ``bruteforce`` — the MXU path: all-pairs leaf tests / distance matrix
    (``BruteForce``). Exact by construction; fastest while N·Q is small
    because a (Q, N) panel is one matmul-shaped pass.
  * ``pallas``     — the fused stackless-traversal kernel
    (``kernels.bvh_traverse``): whole tree staged through VMEM, a block of
    queries per grid cell, one int32 cursor per lane.
  * ``loop``       — the vmapped ``lax.while_loop`` traversal
    (``core.traversal``): fully general (any predicate kind, any value
    geometry, arbitrary callbacks); the fallback whenever a query is not
    expressible in the kernel's unified box/r² form.

Routing is static (Python-level: N, Q, predicate type, value geometry), so
it never traces into jit. The crossover thresholds live in a declarative
:class:`~repro.core.route_table.RouteTable` (autotuned per hardware by
``benchmarks/autotune.py``); lookup order is explicit policy table >
engine-config table > persisted ``ROUTE_TABLE.json`` > built-in defaults,
with ``REPRO_ENGINE_FORCE`` pinning a route outright for A/B runs.
"""
from __future__ import annotations

import collections
import dataclasses
import os
import threading

import jax
import jax.numpy as jnp

from ..kernels.bvh_callback import bvh_traverse_callback
from ..kernels.bvh_traverse import bvh_traverse_knn, bvh_traverse_spatial
from ..telemetry import tracer as TEL
from . import geometry as G
from . import lbvh
from . import predicates as P
from . import route_table as RT

__all__ = ["EngineConfig", "EngineStats", "EngineStatsSnapshot", "ExecInfo",
           "QueryEngine", "default_engine", "set_default_engine",
           "ROUTE_BRUTEFORCE", "ROUTE_PALLAS", "ROUTE_LOOP"]

ROUTE_BRUTEFORCE = "bruteforce"
ROUTE_PALLAS = "pallas"
ROUTE_LOOP = "loop"

#: old EngineConfig crossover field -> RouteRule field (deprecation shims)
_LEGACY_CROSSOVERS = {
    "brute_force_max_work": "bf_max_work",
    "pallas_min_queries": "pallas_min_queries",
    "pallas_min_leaves": "pallas_min_leaves",
    "pallas_max_nodes": "pallas_max_nodes",
    "pallas_max_capacity": "pallas_max_capacity",
}


@dataclasses.dataclass
class EngineConfig:
    """Engine-level knobs. The crossover thresholds themselves live in a
    :class:`~repro.core.route_table.RouteTable` (per-op rules, autotuned
    per hardware); the old per-field constants are warn-once deprecation
    shims that synthesize a single-row table.

    route_table: a RouteTable (or a path to a persisted one) used for
        every routing decision through this engine; None defers to the
        ambient persisted table (``ROUTE_TABLE.json`` /
        ``$REPRO_ROUTE_TABLE``) and finally to the backend's built-in
        table (TPU keeps every op off the traversal kernels). An
        :class:`~repro.core.index.ExecutionPolicy` table overrides this
        per call/index.
    max_executables: LRU bound on the exec_* executable cache — a long-
        lived service whose leaf count changes across rebuilds must not
        pin one compiled executable per historical N forever.
    use_pallas: master switch for the fused kernel paths.
    force: route every eligible query to one path ("bruteforce" |
        "pallas" | "loop"); queries the forced path cannot express fall
        back to the normal heuristic choice. ``REPRO_ENGINE_FORCE`` sets
        this for the whole process (debugging; it beats every table).

    brute_force_max_work / pallas_min_queries / pallas_min_leaves /
    pallas_max_nodes / pallas_max_capacity: DEPRECATED — pass
    ``route_table=RouteTable.single(bf_max_work=..., ...)`` instead.
    """
    route_table: object = None
    use_pallas: bool = True
    force: str | None = None
    max_executables: int = 256
    # DEPRECATED crossover fields (warn-once shims; see _LEGACY_CROSSOVERS)
    brute_force_max_work: int | None = None
    pallas_min_queries: int | None = None
    pallas_min_leaves: int | None = None
    pallas_max_nodes: int | None = None
    pallas_max_capacity: int | None = None

    def __post_init__(self):
        routes = (ROUTE_BRUTEFORCE, ROUTE_PALLAS, ROUTE_LOOP)
        if self.force is not None and self.force not in routes:
            raise ValueError(f"force={self.force!r} is not one of {routes}")
        env = os.environ.get("REPRO_ENGINE_FORCE")
        if self.force is None and env:
            if env not in routes:
                raise ValueError(
                    f"REPRO_ENGINE_FORCE={env!r} is not one of {routes}")
            self.force = env
        if isinstance(self.route_table, (str, os.PathLike)):
            self.route_table = RT.RouteTable.load(os.fspath(self.route_table))
        legacy = {name: getattr(self, name) for name in _LEGACY_CROSSOVERS
                  if getattr(self, name) is not None}
        if legacy:
            from .index import _warn_deprecated
            fields = ", ".join(sorted(legacy))
            _warn_deprecated(
                "EngineConfig.crossovers",
                f"EngineConfig crossover fields ({fields}) are deprecated; "
                "pass route_table=RouteTable.single(...) or autotune one "
                "with `python -m benchmarks.autotune`")
            base = (self.route_table.rule("default")
                    if isinstance(self.route_table, RT.RouteTable)
                    else RT.RouteRule())
            rule = base.replace(**{_LEGACY_CROSSOVERS[k]: int(v)
                                   for k, v in legacy.items()})
            self.route_table = RT.RouteTable(
                rules={"default": rule}, source="synthesized")


def _pallas_spatial_call(tree, q_lo, q_hi, r, *, capacity, fine_sqrt,
                         bq=256):
    """The ONE spelling of the fused spatial kernel call, shared by the
    direct route (pallas_fill) and the cached service executables."""
    return bvh_traverse_spatial(
        tree.node_lo, tree.node_hi, tree.rope, tree.left_child,
        tree.range_last, tree.leaf_perm, q_lo, q_hi, r,
        capacity=capacity, fine_sqrt=fine_sqrt, bq=bq)


def _pallas_knn_call(tree, qc, *, k, bq=256):
    return bvh_traverse_knn(tree.node_lo, tree.node_hi, tree.rope,
                            tree.left_child, tree.leaf_perm, qc, k=k, bq=bq)


#: predicate kinds the fused callback kernel can evaluate in-kernel
#: (``node_overlap_test`` has no spelling for Nearest — kNN has its own
#: kernel); everything else stays on the loop path.
_CALLBACK_KINDS = (P.Intersects, P.RayIntersect, P.RayOrderedIntersect,
                   P.RayNearest)


def _state_width(state0) -> int:
    """Widest per-query state row (elements) across the pytree leaves —
    the VMEM-output analogue of a fill capacity."""
    width = 1
    for leaf in jax.tree_util.tree_leaves(state0):
        w = 1
        for s in jnp.shape(leaf)[1:]:
            w *= int(s)
        width = max(width, w)
    return width


def _spatial_rep(predicates):
    """Unified (q_lo, q_hi, r) form of an Intersects batch, or None when
    the geometry kind has no exact box/radius spelling."""
    if not isinstance(predicates, P.Intersects):
        return None
    g = predicates.geom
    if isinstance(g, G.Points):
        z = jnp.zeros((g.coords.shape[0],), jnp.float32)
        return g.coords, g.coords, z
    if isinstance(g, G.Boxes):
        z = jnp.zeros((g.lo.shape[0],), jnp.float32)
        return g.lo, g.hi, z
    if isinstance(g, G.Spheres):
        return g.center, g.center, g.radius.astype(jnp.float32)
    return None


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
    """Dispatches batched BVH queries to bruteforce / pallas / loop."""

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

    # -- route-table resolution (DESIGN.md §8 lookup order) ---------------
    def table(self, policy=None) -> RT.RouteTable:
        """Resolve the effective RouteTable: explicit policy table >
        engine-config table > ambient persisted table > the built-in
        table for the backend (:func:`~repro.core.route_table.
        builtin_route_table`). (``force`` is orthogonal — checked inside
        ``_pick``.)"""
        t = getattr(policy, "route_table", None)
        if t is None:
            t = self.config.route_table
        if t is None:
            t = RT.default_route_table()
        return t if t is not None else RT.builtin_route_table()

    def _rule(self, op: str, bvh, policy) -> RT.RouteRule:
        if policy is None:
            policy = getattr(bvh, "policy", None)
        return self.table(policy).rule(op)

    # -- routing ----------------------------------------------------------
    def route_spatial(self, bvh, predicates, capacity: int | None = None,
                      *, policy=None) -> str:
        """Route an Intersects batch for count/fill. Ray predicates and
        exotic geometries always take the loop path; fill passes whose
        per-query buffer would blow the VMEM output block stay off pallas."""
        cfg = self.config
        rule = self._rule("spatial", bvh, policy)
        q = len(predicates)
        bf_ok = isinstance(predicates, P.Intersects)
        pl_ok = (cfg.use_pallas and bvh.tree is not None and q > 0
                 and bvh.pallas_values_ok
                 and _spatial_rep(predicates) is not None
                 and 2 * bvh.size() - 1 <= rule.pallas_max_nodes
                 and (capacity is None or capacity <= rule.pallas_max_capacity))
        return self._pick(bvh.size(), q, bf_ok, pl_ok, rule)

    def route_knn(self, bvh, predicates, *, policy=None) -> str:
        cfg = self.config
        rule = self._rule("knn", bvh, policy)
        q = len(predicates)
        bf_ok = isinstance(predicates, P.Nearest)
        pl_ok = (cfg.use_pallas and bvh.tree is not None and bf_ok and q > 0
                 and bvh.pallas_values_ok
                 and predicates.k <= rule.pallas_max_capacity
                 and 2 * bvh.size() - 1 <= rule.pallas_max_nodes)
        return self._pick(bvh.size(), q, bf_ok, pl_ok, rule)

    def route_callback(self, bvh, predicates, state0=None, *,
                       policy=None) -> str:
        """Route a callback-flavor query: fused kernel (callback executes
        in the traversal epilogue, no CSR ever materialized) vs the
        vmapped while loop. Bruteforce cannot run callbacks, so a
        bruteforce force falls back to the heuristic."""
        cfg = self.config
        rule = self._rule("callback", bvh, policy)
        n = bvh.size()
        q = len(predicates)
        pl_ok = (cfg.use_pallas and bvh.tree is not None and q > 0
                 and isinstance(predicates, _CALLBACK_KINDS)
                 and 2 * n - 1 <= rule.pallas_max_nodes
                 and (state0 is None
                      or _state_width(state0) <= rule.pallas_max_capacity))
        if cfg.force == ROUTE_PALLAS:
            return ROUTE_PALLAS if pl_ok else ROUTE_LOOP
        if cfg.force == ROUTE_LOOP:
            return ROUTE_LOOP
        if (pl_ok and q >= rule.pallas_min_queries
                and n >= rule.pallas_min_leaves):
            return ROUTE_PALLAS
        return ROUTE_LOOP

    def _pick(self, n: int, q: int, bf_ok: bool, pl_ok: bool,
              rule: RT.RouteRule | None = None) -> str:
        cfg = self.config
        rule = rule if rule is not None else self.table().rule("spatial")
        if cfg.force == ROUTE_BRUTEFORCE and bf_ok:
            return ROUTE_BRUTEFORCE
        if cfg.force == ROUTE_PALLAS and pl_ok:
            return ROUTE_PALLAS
        if cfg.force == ROUTE_LOOP:
            return ROUTE_LOOP
        if bf_ok and n * q <= rule.bf_max_work:
            return ROUTE_BRUTEFORCE
        if (pl_ok and q >= rule.pallas_min_queries
                and n >= rule.pallas_min_leaves):
            return ROUTE_PALLAS
        return ROUTE_LOOP

    # -- pallas execution --------------------------------------------------
    def pallas_count(self, bvh, predicates, *, policy=None):
        """(Q,) int32 match counts via the fused kernel."""
        counts, _ = self.pallas_fill(bvh, predicates, 1, policy=policy)
        return counts

    def pallas_fill(self, bvh, predicates, capacity: int, *, policy=None):
        """(counts, idx_buf): the ``collect_hits`` contract — full counts
        plus the first `capacity` matched indices in traversal order."""
        q_lo, q_hi, r = _spatial_rep(predicates)
        # Points values take the sqrt-form fine test (distance <= r), the
        # bit-exact twin of predicates.leaf_match_test for them
        return _pallas_spatial_call(bvh.tree, q_lo, q_hi, r,
                                    capacity=capacity,
                                    fine_sqrt=isinstance(bvh.values, G.Points),
                                    bq=self._rule("spatial", bvh, policy).block_q)

    def pallas_knn(self, bvh, predicates, *, policy=None):
        """(dists, idxs) (Q, k) via the fused kernel. Query point is the
        geometry centroid — exactly what ``predicates.leaf_distance``
        measures fine distances from."""
        return _pallas_knn_call(bvh.tree, G.centroid(predicates.geom),
                                k=predicates.k,
                                bq=self._rule("knn", bvh, policy).block_q)

    def pallas_callback(self, bvh, predicates, callback, state0, *,
                        policy=None):
        """Per-query final states via the fused callback kernel —
        bit-identical to ``traversal.traverse`` (the conformance tests pin
        it), but the callback runs inside the kernel loop."""
        t = bvh.tree
        return bvh_traverse_callback(
            t.node_lo, t.node_hi, t.rope, t.left_child, t.range_last,
            t.leaf_perm, bvh.values, predicates, callback, state0,
            bq=self._rule("callback", bvh, policy).block_q)

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
        bq = self._rule("spatial", bvh, None).block_q
        # every value a traced body closes over is named IN the key —
        # reprolint TRC004 pins this (a closed-over value missing from the
        # key would let two different executables share one cache slot)
        nq = len(predicates)
        fine_sqrt = isinstance(bvh.values, G.Points)
        getter = bvh._getter
        key = (route, "spatial", capacity, bq, nq, fine_sqrt,
               getter) + self._shape_key(bvh, predicates)

        if route == ROUTE_PALLAS:
            def make():
                def body(tree, q_lo, q_hi, r):
                    self._note_trace()
                    return _pallas_spatial_call(tree, q_lo, q_hi, r,
                                                capacity=capacity,
                                                fine_sqrt=fine_sqrt, bq=bq)
                return jax.jit(body)

            fn, hit = self._cached(key, make)
            q_lo, q_hi, r = _spatial_rep(predicates)
            out, kus = self._launch(fn, (bvh.tree, q_lo, q_hi, r),
                                    route=route, op="spatial", hit=hit)
            return out, ExecInfo(route, hit, kus)

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
        bq = self._rule("knn", bvh, None).block_q
        getter = bvh._getter
        key = (route, "knn", k, bq, getter) + self._shape_key(bvh, predicates)

        if route == ROUTE_PALLAS:
            def make():
                def body(tree, qc):
                    self._note_trace()
                    return _pallas_knn_call(tree, qc, k=k, bq=bq)
                return jax.jit(body)

            fn, hit = self._cached(key, make)
            out, kus = self._launch(fn, (bvh.tree, G.centroid(predicates.geom)),
                                    route=route, op="knn", hit=hit)
            return out, ExecInfo(route, hit, kus)

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

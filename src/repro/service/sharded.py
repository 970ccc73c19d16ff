"""Sharded serving: DistributedTree behind the service layer (DESIGN.md §11).

The single-device serving stack (IndexStore -> Batcher -> QueryServer /
ServingPipeline) stops at one device; this module is its SPMD analogue,
the serving-side counterpart of ArborX's distributed tree (§2.3):

  * :class:`ShardedIndexStore` — a mesh-aware :class:`IndexStore`: builds
    publish :class:`~repro.core.distributed.DistributedTree` indexes (one
    local LBVH per shard under ``shard_map``), updates run PR 4's
    topology-reuse refit INDEPENDENTLY on every shard plus a cheap
    re-exchange of the per-shard top bounds, and everything lands through
    the inherited atomic version swap / ``pin``/``release``/``pinned``
    refcounting — serving never stalls behind maintenance.
  * :class:`ShardedExecutor` — the group launcher ``launch_group``
    routes to whenever a batch names a sharded index: predicates are
    all-gathered, every shard answers against local data, partial results
    ``all_to_all`` back to the originating shard and merge (top-k by
    distance, psum for counts). Each phase is a separately-jitted
    ``shard_map`` stage so telemetry can fence and attribute device time
    to gather / local-traverse / exchange / merge (unfenced under a
    profiler alone, each stage's span times its enqueue).

Refit quality is monitored PER SHARD: drift is rarely uniform, so the
store tracks an (R,)-tuple of SAH costs and a single shard degrading past
``rebuild_threshold`` triggers the shadow rebuild — exactly the
"worst-rank decides" policy a distributed SAH monitor needs. Refit swaps
go through :meth:`DistributedTree.from_local_trees`, so no re-sort and no
re-gather of the top index on the fast path.
"""
from __future__ import annotations

import dataclasses
import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as PS

from repro.compat import shard_map

from ..core import callbacks as CB
from ..core import geometry as G
from ..core import lbvh
from ..core import predicates as P
from ..core import traversal as T
from ..core.access import default_indexable_getter
from ..core.distributed import DistributedTree
from ..core.index import _bcast_state
from ..telemetry import tracer as TEL
from .batcher import KIND_KNN, KIND_WITHIN, Group, _pad_edge
from .index_store import IndexStore
from .server import Launched, RequestStats, ServiceConfig

__all__ = ["ShardedExecutor", "ShardedIndexStore", "ShardedIndexVersion"]


@dataclasses.dataclass(frozen=True)
class ShardedIndexVersion:
    """Immutable snapshot of one published sharded index version.

    Mirrors :class:`~repro.service.index_store.IndexVersion` (same swap /
    pin machinery applies) with per-SHARD quality: ``sah``/``sah_built``
    are (R,)-tuples and :attr:`degradation` reports the worst shard —
    one bad shard is enough to warrant the shadow rebuild."""
    name: str
    version: int
    tree: DistributedTree
    action: str                 # "build" | "refit" | "rebuild"
    sah: tuple                  # per-shard quality of THIS tree
    sah_built: tuple            # per-shard quality at the last full build
    refits_since_build: int
    executor: "ShardedExecutor" = dataclasses.field(repr=False)

    #: duck-typed routing flag read by ``server.launch_group``
    sharded = True

    @property
    def degradation(self) -> float:
        """Worst shard's SAH cost relative to its at-build cost."""
        return max(s / max(b, 1e-30)
                   for s, b in zip(self.sah, self.sah_built))

    @property
    def dim(self) -> int:
        return int(self.tree.dim)


@dataclasses.dataclass(frozen=True)
class _StagePlan:
    """The four jitted shard_map stages for one (kind, k, capacity,
    n_local) shape family. Trees/values arrive as ARGUMENTS so refit swaps
    of the same index reuse warm executables."""
    gather: callable
    local: callable
    exchange: callable
    merge: callable


class ShardedExecutor:
    """Launches planned :class:`~repro.service.batcher.Group` batches
    against a pinned :class:`ShardedIndexVersion` as a four-stage
    collective pipeline. Staging exists for ATTRIBUTION: when telemetry is
    enabled each stage is device-fenced under its own span, so the report
    CLI shows where distributed time goes; with telemetry off the fences
    are no-ops and XLA overlaps the stages asynchronously as usual."""

    #: reprolint lock discipline: the stage-plan cache is shared by every
    #: thread dispatching against this mesh (server + pipeline scheduler)
    _REPROLINT_GUARDED_BY = {"_stages": "_lock"}

    def __init__(self, mesh, axis: str):
        self.mesh = mesh
        self.axis = axis
        self.R = int(mesh.shape[axis])
        self._lock = threading.Lock()
        self._stages: dict[tuple, _StagePlan] = {}

    # -- plan construction ---------------------------------------------------
    def _plan(self, kind: str, k: int, capacity: int,
              n_local: int) -> tuple[_StagePlan, bool]:
        key = (kind, k, capacity, n_local)
        with self._lock:
            plan = self._stages.get(key)
            warm = plan is not None
            if plan is None:
                # building a plan only wraps closures in jit (no tracing),
                # so holding the lock here is cheap
                plan = self._build_plan(kind, k, capacity, n_local)
                self._stages[key] = plan
        return plan, warm

    def _build_plan(self, kind, k, capacity, n_local) -> _StagePlan:
        mesh, axis, R = self.mesh, self.axis, self.R
        spec = PS(axis)
        rep = PS()
        col = PS(None, axis)    # (R, Q, ...) sharded over the QUERY dim

        def smap(f, in_specs, out_specs):
            return jax.jit(shard_map(f, mesh=mesh, in_specs=in_specs,
                                     out_specs=out_specs, check_vma=False))

        # stage 1 — gather: all-gather each shard's slice of the query
        # batch so every shard holds the full (Qp, ...) predicate arrays
        def gather1(a):
            return (jax.lax.all_gather(a, axis, tiled=True),)

        def gather2(a, b):
            return (jax.lax.all_gather(a, axis, tiled=True),
                    jax.lax.all_gather(b, axis, tiled=True))

        if kind == KIND_KNN:
            gather = smap(gather1, (spec,), (rep,))
        else:
            gather = smap(gather2, (spec, spec), (rep, rep))

        # stage 2 — local traverse: every shard answers ALL queries
        # against its local tree; matched indices globalize to shard-
        # relative row offsets (callbacks would run here, data-side)
        def globalize(i):
            r = jax.lax.axis_index(axis)
            return jnp.where(i >= 0, i + r * n_local, -1)

        if kind == KIND_WITHIN:
            def local_fn(trees, vals, centers, radii):
                preds = P.intersects(G.Spheres(centers, radii))
                cb, s0 = CB.collect_hits(capacity)
                s0 = _bcast_state(s0, centers.shape[0])
                count, idxs, _ = T.traverse(trees, vals, preds, cb, s0)
                return count, globalize(idxs)

            local = smap(local_fn, (spec, spec, rep, rep), (spec, spec))

            # stage 3 — exchange: per-shard partials return to the shard
            # owning each query row (R * capacity candidates per query)
            def exch_fn(count, gi):
                qloc = count.shape[0] // R
                count = jax.lax.all_to_all(
                    count.reshape(R, qloc), axis, 0, 0)
                gi = jax.lax.all_to_all(
                    gi.reshape(R, qloc, capacity), axis, 0, 0)
                return count, gi

            exchange = smap(exch_fn, (spec, spec), (col, col))

            # stage 4 — merge: full counts psum across shards; index
            # buffers pack valid-first and clamp to the serving capacity
            def merge_fn(count, gi):
                qloc = gi.shape[1]
                gi = jnp.moveaxis(gi, 0, 1).reshape(qloc, R * capacity)
                order = jnp.argsort((gi < 0).astype(jnp.int32), axis=1,
                                    stable=True)
                buf = jnp.take_along_axis(gi, order, 1)[:, :capacity]
                total = jnp.moveaxis(count, 0, 1).sum(1).astype(jnp.int32)
                return total, buf

            merge = smap(merge_fn, (col, col), (spec, spec))
        else:
            # knn and ray-nearest share the candidate-merge shape: (Q, k)
            # distances (ray parameter t for rays) + global indices
            def local_fn(trees, vals, a_all, b_all=None):
                if kind == KIND_KNN:
                    preds = P.nearest(G.Points(a_all), k=k)
                else:
                    preds = P.RayNearest(G.Rays(a_all, b_all), k)
                d, i = T.traverse_knn(trees, vals, preds, k)
                return d, globalize(i)

            if kind == KIND_KNN:
                local = smap(local_fn, (spec, spec, rep), (spec, spec))
            else:
                local = smap(local_fn, (spec, spec, rep, rep), (spec, spec))

            def exch_fn(d, gi):
                qloc = d.shape[0] // R
                d = jax.lax.all_to_all(d.reshape(R, qloc, k), axis, 0, 0)
                gi = jax.lax.all_to_all(gi.reshape(R, qloc, k), axis, 0, 0)
                return d, gi

            exchange = smap(exch_fn, (spec, spec), (col, col))

            def merge_fn(d, gi):
                qloc = d.shape[1]
                d = jnp.moveaxis(d, 0, 1).reshape(qloc, R * k)
                gi = jnp.moveaxis(gi, 0, 1).reshape(qloc, R * k)
                order = jnp.argsort(d, axis=1)[:, :k]
                return (jnp.take_along_axis(d, order, 1),
                        jnp.take_along_axis(gi, order, 1))

            merge = smap(merge_fn, (col, col), (spec, spec))

        return _StagePlan(gather, local, exchange, merge)

    # -- dispatch ------------------------------------------------------------
    def launch_group(self, config: ServiceConfig, entry: ShardedIndexVersion,
                     group: Group) -> Launched:
        """Enqueue ONE planned group's four stages against a pinned
        sharded version — the sharded counterpart of
        ``server.launch_group`` (which routes here); ``server.finish_group``
        waits for the merged answers, copies them back and scatters them."""
        tree = entry.tree
        R = self.R
        # shard_map needs the batch divisible by R; buckets are powers of
        # two >= min_bucket so this only pads tiny buckets on wide meshes
        qp = -(-group.bucket // R) * R
        a = _pad_edge(group.a, qp)
        b = None if group.b is None else _pad_edge(group.b, qp)
        cap = config.capacity if group.kind == KIND_WITHIN else 0
        plan, warm = self._plan(group.kind, group.k, cap, tree.n_local)

        args = ((jnp.asarray(a),) if group.kind == KIND_KNN
                else (jnp.asarray(a), jnp.asarray(b)))
        kernel_us = 0.0
        with TEL.span("sharded.execute_group", kind=group.kind,
                      bucket=group.bucket, shards=R, index=entry.name,
                      version=entry.version):
            with TEL.span("sharded.gather", kind=group.kind, q=qp) as sp:
                gathered = sp.fence(plan.gather(*args))
            kernel_us += sp.dur_us
            with TEL.span("sharded.local_traverse", kind=group.kind,
                          n_local=tree.n_local,
                          node_record_lanes=lbvh.node_record_lanes(
                              tree.dim)) as sp:
                partial = sp.fence(plan.local(tree.trees, tree.values,
                                              *gathered))
            kernel_us += sp.dur_us
            with TEL.span("sharded.exchange", kind=group.kind) as sp:
                exchanged = sp.fence(plan.exchange(*partial))
            kernel_us += sp.dur_us
            with TEL.span("sharded.merge", kind=group.kind) as sp:
                merged = sp.fence(plan.merge(*exchanged))
            kernel_us += sp.dur_us
        stats = RequestStats(
            kind=group.kind, route="sharded", bucket=group.bucket,
            index_name=entry.name, index_version=entry.version,
            cache_hit=warm, kernel_us=kernel_us)
        return Launched(group, tuple(merged), stats, config.capacity)


# -- shard-local maintenance steps (cached: jit reuses warm executables
# across every update of every store sharing a (mesh, axis, getter)) -------

@functools.lru_cache(maxsize=64)
def _sah_step(mesh, axis):
    spec = PS(axis)

    def step(trees):
        return lbvh.sah_cost(trees)[None]

    return jax.jit(shard_map(step, mesh=mesh, in_specs=(spec,),
                             out_specs=spec, check_vma=False))


@functools.lru_cache(maxsize=64)
def _refit_step(mesh, axis, getter):
    spec = PS(axis)

    def step(trees, vals_local):
        new, sah = lbvh.refit_with_quality(trees, getter(vals_local))
        # top bounds re-exchange rides the same out_specs concat: each
        # shard contributes its refitted root box (1, dim) -> (R, dim)
        return new, (new.node_lo[:1], new.node_hi[:1]), sah[None]

    return jax.jit(shard_map(step, mesh=mesh, in_specs=(spec, spec),
                             out_specs=(spec, (spec, spec), spec),
                             check_vma=False))


class ShardedIndexStore(IndexStore):
    """Thread-safe name -> :class:`ShardedIndexVersion` registry over a
    device mesh.

    Same contract as :class:`IndexStore` — atomic swap under the registry
    lock, history ring, pin refcounts protecting in-flight batches from
    trimming — but entries wrap a :class:`DistributedTree` and updates run
    the distributed refit: one topology-reuse refit per shard plus a
    re-exchange of per-shard top bounds, falling back to a shadow rebuild
    when ANY shard's SAH monitor degrades past ``rebuild_threshold``."""

    # registry maps + pins inherit IndexStore's _REPROLINT_GUARDED_BY
    # declaration; this subclass only calls the base's locked methods.

    def __init__(self, mesh, axis: str, engine=None, *,
                 rebuild_threshold: float = 1.5, keep_versions: int = 3,
                 policy=None):
        if axis not in mesh.shape:
            raise ValueError(f"axis {axis!r} is not an axis of the mesh "
                             f"(axes: {tuple(mesh.axis_names)})")
        super().__init__(engine, rebuild_threshold=rebuild_threshold,
                         keep_versions=keep_versions)
        self.mesh = mesh
        self.axis = axis
        self.policy = policy
        self.executor = ShardedExecutor(mesh, axis)

    # -- writes --------------------------------------------------------------
    def build(self, name: str, values,
              indexable_getter=default_indexable_getter):
        """Build per-shard local trees and atomically publish the next
        version (values' leading axis must divide by the shard count)."""
        return self._publish_sharded(name, values, indexable_getter,
                                     action="build")

    def update(self, name: str, values):
        """Distributed refit-or-rebuild: refit every shard's local tree
        independently (no cross-shard traffic beyond the (R, dim) top-bound
        exchange), rebuild when the leaf count changed or the WORST shard
        degraded past threshold. Runs outside the registry lock; only the
        finished version swaps in."""
        cur = self.get(name)
        tree = cur.tree
        getter = tree._getter
        values = DistributedTree._adapt_values(values, getter)
        if len(getter(values)) != tree.size():
            return self._publish_sharded(name, values, getter,
                                         action="rebuild")

        with TEL.span("store.refit", index=name, n=tree.size(),
                      shards=tree.R) as sp:
            trees, (top_lo, top_hi), sah = sp.fence(
                _refit_step(self.mesh, self.axis, getter)(tree.trees, values))
            sah = tuple(float(s) for s in np.asarray(sah))
            sp.annotate(degradation=max(
                s / max(b, 1e-30) for s, b in zip(sah, cur.sah_built)))
        if any(s > self.rebuild_threshold * b
               for s, b in zip(sah, cur.sah_built)):
            return self._publish_sharded(name, values, getter,
                                         action="rebuild")

        new_tree = DistributedTree.from_local_trees(
            self.mesh, self.axis, values, trees, top_lo, top_hi, getter,
            policy=tree.policy)
        return self._swap(ShardedIndexVersion(
            name=name, version=0, tree=new_tree, action="refit", sah=sah,
            sah_built=cur.sah_built,
            refits_since_build=cur.refits_since_build + 1,
            executor=self.executor))

    # -- internals -----------------------------------------------------------
    def _publish_sharded(self, name, values, getter, *, action):
        with TEL.span("store.build", index=name, action=action,
                      sharded=True) as sp:
            tree = DistributedTree(self.mesh, self.axis, values, getter,
                                   policy=self.policy)
            sah = sp.fence(_sah_step(self.mesh, self.axis)(tree.trees))
            sah = tuple(float(s) for s in np.asarray(sah))
            sp.annotate(n=tree.size(), shards=tree.R)
        return self._swap(ShardedIndexVersion(
            name=name, version=0, tree=tree, action=action, sah=sah,
            sah_built=sah, refits_since_build=0, executor=self.executor))

"""QueryServer: the synchronous service frontend (DESIGN.md §5).

One ``handle(requests)`` call is a scheduling quantum: the batcher plans
shape-stable groups, each group is dispatched once through the engine's
executable cache against a *pinned* index version (grabbed at dispatch
time; concurrent ``update_index`` swaps never tear a batch), and results
scatter back to per-request :class:`Response` objects carrying stats —
which route served it, which bucket it rode in, which index version it
saw, and whether the executable was warm.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..core import engine as E
from ..core import geometry as G
from ..core import predicates as P
from ..core.access import default_indexable_getter
from ..telemetry import tracer as TEL
from .batcher import (KIND_KNN, KIND_RAY, KIND_WITHIN, Batcher, Group,
                      Request, bucket_size, knn_request, ray_request,
                      within_request)
from .index_store import IndexStore, IndexVersion

__all__ = ["ServiceConfig", "RequestStats", "Response", "QueryServer",
           "Launched", "launch_group", "finish_group", "execute_group"]


@dataclasses.dataclass(frozen=True)
class ServiceConfig:
    """capacity: CSR buffer width per within-radius query. Held FIXED so
    every within bucket reuses one executable; requests that overflow it
    are flagged (callers needing exact spill re-issue via ``BVH.query``,
    which auto-retries with doubled capacity).
    min_bucket / max_bucket: the power-of-two bucket ladder — min_bucket is
    the smallest (and alignment of) bucket, max_bucket the largest batch
    one dispatch carries (warmup covers the whole ladder; the async
    pipeline closes a group when it reaches max_bucket rows).
    rebuild_threshold: SAH degradation ratio that turns a refit into a
    full rebuild (forwarded to the IndexStore the server creates)."""
    capacity: int = 64
    min_bucket: int = 8
    max_bucket: int = 128
    rebuild_threshold: float = 1.5


@dataclasses.dataclass(frozen=True)
class RequestStats:
    kind: str
    route: str            # bruteforce | loop
    bucket: int           # power-of-two batch the request rode in
    index_name: str
    index_version: int
    cache_hit: bool       # executable was already warm
    # async-pipeline timing (zero on the synchronous QueryServer path):
    queue_wait_us: float = 0.0    # submit -> batch dispatch
    service_us: float = 0.0       # batch dispatch -> results ready
    deadline_us: float | None = None
    deadline_missed: bool = False
    # telemetry (DESIGN.md §10; zero/None unless telemetry is enabled —
    # kernel_us needs a device fence the disabled path must not pay):
    kernel_us: float = 0.0        # device-fenced engine executable time
    span_id: int = 0              # "request" root span id in the trace
    phase_us: dict | None = None  # REQUEST_PHASES tiling (async pipeline)


@dataclasses.dataclass(frozen=True)
class Response:
    """Per-request results. knn/ray fill (dists, idxs) (m, k) — dists are
    ray-hit parameters t for ray requests; within fills (counts, idxs)
    with idxs (m, capacity) -1-padded and `overflow` set when any query
    matched more than `capacity`. `steps` (m,) is the nodes each row's
    kNN loop read, and `seeded` (m,) whether its pruning bound was seeded,
    where the engine's loop route served it."""
    stats: RequestStats
    dists: np.ndarray | None = None
    idxs: np.ndarray | None = None
    counts: np.ndarray | None = None
    overflow: bool = False
    steps: np.ndarray | None = None
    seeded: np.ndarray | None = None


@dataclasses.dataclass(frozen=True)
class Launched:
    """A group whose device work is enqueued but not awaited: the bucket's
    result arrays, still on the device — (counts, idxs) for within,
    (dists, idxs) otherwise — and the stats every member's Response
    carries. :func:`finish_group` completes it."""
    group: Group
    arrays: tuple
    stats: RequestStats
    capacity: int         # within: counts above it flag overflow
    knn_counts: object = None  # kNN loop route: (2, bucket) steps, seeded


def launch_group(engine: E.QueryEngine, config: ServiceConfig,
                 entry: IndexVersion, group: Group) -> Launched:
    """Enqueue ONE planned group's device work against a pinned index
    version and return without waiting for it (a sharded version hands
    the group to its mesh's ``ShardedExecutor``)."""
    if getattr(entry, "sharded", False):
        # sharded versions carry their mesh's ShardedExecutor: collective
        # staged dispatch replaces the engine's single-device executable
        # cache (duck-typed so this module never imports service.sharded)
        return entry.executor.launch_group(config, entry, group)
    bvh = entry.bvh
    with TEL.span("server.execute_group", kind=group.kind,
                  bucket=group.bucket, index=entry.name,
                  version=entry.version):
        a = jnp.asarray(group.a)
        # degenerate indexes (N < 2) have no tree; the engine's cached
        # executables need one, but the BVH API itself linear-scans — a
        # cloud that shrinks to one point must not take down serving
        tiny = bvh.tree is None
        info = E.ExecInfo(E.ROUTE_LOOP, False) if tiny else None

        if group.kind == KIND_WITHIN:
            preds = P.intersects(G.Spheres(a, jnp.asarray(group.b)))
            if tiny:
                arrays = bvh._fill_impl(preds, config.capacity, bvh.policy)
            else:
                arrays, info = engine.exec_spatial(bvh, preds,
                                                   config.capacity)
        elif group.kind == KIND_KNN:
            preds = P.nearest(G.Points(a), k=group.k)
            if tiny:
                res = bvh.query(preds)
                arrays = (res.distances, res.indices)
            else:
                arrays, info = engine.exec_knn(bvh, preds)
        else:  # KIND_RAY
            rays = G.Rays(a, jnp.asarray(group.b))
            if tiny:
                res = bvh.query(P.RayNearest(rays, group.k))
                arrays = (res.distances, res.indices)
            else:
                arrays, info = engine.exec_ray_nearest(bvh, rays, group.k)
    stats = RequestStats(kind=group.kind, route=info.route,
                         bucket=group.bucket, index_name=entry.name,
                         index_version=entry.version,
                         cache_hit=info.cache_hit, kernel_us=info.kernel_us)
    return Launched(group, tuple(arrays), stats, config.capacity,
                    info.knn_counts)


def finish_group(launched: Launched) -> dict[int, "Response"]:
    """Wait for a launched group's device work, copy its results to the
    host and scatter them to per-request Responses (keyed by the request
    ids recorded in ``group.members``). Each step switches the calling
    thread's phase (``device_wait``, ``copy_back``, ``deliver``) when it
    runs a ``telemetry.phases`` block, as the pipeline's scheduler does."""
    group = launched.group
    TEL.phase("device_wait")
    jax.block_until_ready((launched.arrays, launched.knn_counts))
    TEL.phase("copy_back")
    # CSR assembly: device buffers -> host arrays (+ overflow flags)
    with TEL.span("server.assemble", kind=group.kind):
        first, idxs = (np.asarray(x) for x in launched.arrays)
        loop = (None if launched.knn_counts is None
                else np.asarray(launched.knn_counts))
    TEL.phase("deliver")
    out: dict[int, Response] = {}
    with TEL.span("server.scatter", requests=len(group.members)):
        if group.kind == KIND_WITHIN:
            over = first > launched.capacity
            for rid, start, m in group.members:
                sl = slice(start, start + m)
                out[rid] = Response(launched.stats, counts=first[sl],
                                    idxs=idxs[sl],
                                    overflow=bool(over[sl].any()))
        else:
            for rid, start, m in group.members:
                sl = slice(start, start + m)
                out[rid] = Response(launched.stats, dists=first[sl],
                                    idxs=idxs[sl],
                                    steps=None if loop is None
                                    else loop[0, sl],
                                    seeded=None if loop is None
                                    else loop[1, sl].astype(bool))
    return out


def execute_group(engine: E.QueryEngine, config: ServiceConfig,
                  entry: IndexVersion, group: Group) -> dict[int, "Response"]:
    """Dispatch ONE planned group against a pinned index version and scatter
    the bucket results back to per-request Responses: :func:`launch_group`
    then :func:`finish_group`. Shared by the synchronous
    ``QueryServer.handle`` and the async ``ServingPipeline`` — the caller
    owns version pinning and any timing bookkeeping."""
    return finish_group(launch_group(engine, config, entry, group))


class QueryServer:
    def __init__(self, store: IndexStore | None = None,
                 engine: E.QueryEngine | None = None,
                 config: ServiceConfig | None = None):
        self.config = config or ServiceConfig()
        if store is not None:
            self.store = store
            self.engine = engine if engine is not None else store.engine
        else:
            self.engine = engine if engine is not None else E.QueryEngine()
            self.store = IndexStore(
                self.engine,
                rebuild_threshold=self.config.rebuild_threshold)
        self.batcher = Batcher(self.config.min_bucket)

    # -- index lifecycle ---------------------------------------------------
    def create_index(self, name: str, values,
                     indexable_getter=default_indexable_getter) -> IndexVersion:
        return self.store.build(name, values, indexable_getter)

    def update_index(self, name: str, values) -> IndexVersion:
        """Refit-or-rebuild to moved values; see IndexStore.update."""
        return self.store.update(name, values)

    # -- serving -----------------------------------------------------------
    def handle(self, requests: list[Request]) -> list[Response]:
        """Serve a batch of heterogeneous requests; responses align with
        the input order."""
        responses: list[Response | None] = [None] * len(requests)
        for group in self.batcher.plan(requests):
            self._dispatch(group, responses)
        return responses  # type: ignore[return-value]

    def warmup(self, index: str, kinds_ks: list[tuple[str, int]] | None = None,
               max_bucket: int | None = None, dim: int | None = None,
               default_ks: tuple[int, ...] = (1,)):
        """Pre-trace every (kind, k, bucket) executable for buckets up to
        (and including) the one `max_bucket` queries would ride in, so live
        traffic sees only warm dispatches.

        ALL THREE kinds are warmed by default: any kind absent from
        `kinds_ks` (or all of them, when it is None) is warmed with
        `default_ks` (within always rides k=0 — k doesn't shape its
        result). `max_bucket` defaults to the configured ladder top and
        `dim` is read off the index, so ``warmup("default")`` alone leaves
        no cold route behind."""
        kinds_ks = list(kinds_ks or [])
        have = {kind for kind, _ in kinds_ks}
        for kind in (KIND_KNN, KIND_WITHIN, KIND_RAY):
            if kind not in have:
                kinds_ks += [(kind, 0)] if kind == KIND_WITHIN else \
                            [(kind, k) for k in default_ks]
        if max_bucket is None:
            max_bucket = self.config.max_bucket
        if dim is None:
            dim = self.store.get(index).dim

        b = self.config.min_bucket
        top = bucket_size(max_bucket, self.config.min_bucket)
        while b <= top:
            reqs = []
            for kind, k in kinds_ks:
                a = np.zeros((b, dim), np.float32)
                if kind == KIND_WITHIN:
                    reqs.append(within_request(a, 0.0, index))
                elif kind == KIND_RAY:
                    reqs.append(ray_request(a, np.ones((b, dim), np.float32),
                                            k, index))
                else:
                    reqs.append(knn_request(a, k, index))
            self.handle(reqs)
            b *= 2

    # -- internals ---------------------------------------------------------
    def _dispatch(self, group: Group, responses: list):
        # Pin, not get: a concurrent update_index swap during the dispatch
        # must not let history trimming evict the version this batch runs
        # against (the docstring's "pinned index version" promise).
        with self.store.pinned(group.index) as entry:
            for rid, resp in execute_group(self.engine, self.config,
                                           entry, group).items():
                responses[rid] = resp

"""Sharded serving (DESIGN.md §11): ShardedIndexStore + ShardedExecutor.

Single-shard meshes run in-process (the collective code paths are
identical); multi-shard semantics run in subprocesses with 8 fake host
devices (conftest.run_subprocess). The acceptance pin lives here:
a distributed refit publishing MID-FLIGHT while the in-flight batch
completes on its pinned version.
"""
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import make_mesh
from repro.core import geometry as G
from repro.core.distributed import DistributedTree
from repro.core.engine import EngineConfig, QueryEngine
from repro.service import (IndexStore, PipelineConfig, QueryServer,
                           ServiceConfig, ServingPipeline, ShardedIndexStore,
                           knn_request, ray_request, within_request)

N, DIM = 64, 3


def _pts(n=N, seed=0):
    return np.random.default_rng(seed).uniform(
        0, 1, (n, DIM)).astype(np.float32)


def _mesh1():
    return make_mesh((1,), ("data",))


def _cfg(**kw):
    return ServiceConfig(capacity=kw.pop("capacity", 8), min_bucket=8,
                         max_bucket=32, **kw)


# ---------------------------------------------------------------------------
# store lifecycle: build / refit / rebuild / pins
# ---------------------------------------------------------------------------

def test_sharded_store_build_refit_rebuild_actions():
    store = ShardedIndexStore(_mesh1(), "data")
    pts = _pts(seed=1)
    e1 = store.build("pts", pts)
    assert (e1.version, e1.action) == (1, "build")
    assert e1.sharded and e1.dim == DIM
    assert len(e1.sah) == 1 and e1.sah == e1.sah_built
    assert e1.degradation == pytest.approx(1.0)

    # small drift: topology reuse, per-shard refit
    e2 = store.update("pts", G.Points(jnp.asarray(pts + 0.01)))
    assert (e2.version, e2.action) == (2, "refit")
    assert e2.refits_since_build == 1 and e2.sah_built == e1.sah_built

    # scrambled cloud: SAH monitor trips, shadow rebuild
    e3 = store.update("pts", G.Points(jnp.asarray(
        np.random.default_rng(2).permutation(pts) * 4)))
    assert e3.action == "rebuild" and e3.refits_since_build == 0

    # leaf count changed: topology can't be reused
    e4 = store.update("pts", _pts(32, seed=3))
    assert e4.action == "rebuild" and e4.tree.size() == 32


def test_sharded_store_pins_survive_trimming():
    store = ShardedIndexStore(_mesh1(), "data", keep_versions=1)
    pts = _pts(seed=4)
    store.build("pts", pts)
    pinned = store.pin("pts")
    for tag in (1, 2, 3):
        store.update("pts", G.Points(jnp.asarray(pts + np.float32(tag))))
    assert store.get("pts").version == 4
    assert store.get("pts", 1) is pinned        # keep_versions=1 + pin holds
    store.release(pinned)
    with pytest.raises(KeyError):
        store.get("pts", 1)


def test_sharded_store_rejects_unknown_axis():
    with pytest.raises(ValueError, match="not an axis"):
        ShardedIndexStore(_mesh1(), "nope")


# ---------------------------------------------------------------------------
# serving parity on a single-shard mesh
# ---------------------------------------------------------------------------

def test_sharded_serving_matches_single_device():
    pts = _pts(seed=5)
    cfg = _cfg()
    sharded = QueryServer(store=ShardedIndexStore(_mesh1(), "data"),
                          config=cfg)
    sharded.create_index("default", pts)
    # the shards walk the loop: so does the reference, so that an
    # over-capacity row keeps the same first hits
    plain = QueryServer(store=IndexStore(QueryEngine(EngineConfig(
        force="loop"))), config=cfg)
    plain.create_index("default", G.Points(jnp.asarray(pts)))

    qa = _pts(5, seed=6)
    dirs = np.random.default_rng(7).normal(size=(5, DIM)).astype(np.float32)
    reqs = [knn_request(qa, 3), within_request(qa, 0.3),
            ray_request(qa, dirs, 2)]
    got, want = sharded.handle(list(reqs)), plain.handle(list(reqs))

    assert got[0].stats.route == "sharded"
    assert np.allclose(got[0].dists, want[0].dists, atol=1e-6)
    assert np.array_equal(got[0].idxs, want[0].idxs)
    assert np.array_equal(got[1].counts, want[1].counts)
    assert got[1].overflow == want[1].overflow
    for g, w in zip(got[1].idxs, want[1].idxs):
        assert set(g[g >= 0].tolist()) == set(w[w >= 0].tolist())
    assert np.allclose(got[2].dists, want[2].dists, atol=1e-5)


def test_sharded_warmup_leaves_plans_warm():
    store = ShardedIndexStore(_mesh1(), "data")
    srv = QueryServer(store=store, config=_cfg())
    srv.create_index("default", _pts(seed=8))
    srv.warmup("default")          # dim read off the sharded entry
    (resp,) = srv.handle([knn_request(_pts(4, seed=9), 1)])
    assert resp.stats.cache_hit    # warmup covered (knn, k=1, bucket 8)


def test_sharded_executor_pads_bucket_to_shard_multiple():
    # min_bucket 2 with a 1-shard mesh keeps bucket=2 legal; the executor
    # pads to a multiple of R internally and slices results back
    cfg = ServiceConfig(capacity=4, min_bucket=2, max_bucket=8)
    srv = QueryServer(store=ShardedIndexStore(_mesh1(), "data"), config=cfg)
    srv.create_index("default", _pts(seed=10))
    (resp,) = srv.handle([knn_request(_pts(2, seed=11), 2)])
    assert resp.idxs.shape == (2, 2)


# ---------------------------------------------------------------------------
# the acceptance pin: refit publishes mid-flight, batch stays on its pin
# ---------------------------------------------------------------------------

def test_distributed_refit_publishes_mid_flight_on_pinned_version(
        monkeypatch):
    """A distributed refit completing while a batch is in flight swaps in
    atomically; the in-flight batch still resolves and serves the version
    it pinned at dispatch time (keep_versions=1 would have evicted it)."""
    from repro.service import server as SRV

    pts = _pts(seed=12)
    store = ShardedIndexStore(_mesh1(), "data", keep_versions=1)
    srv = QueryServer(store=store, config=_cfg())
    srv.create_index("pts", pts)

    real = SRV.execute_group
    observed = {}

    def racing_execute(engine, config, entry, group):
        for tag in (1, 2, 3):                   # refits land mid-dispatch
            pub = store.update("pts", G.Points(
                jnp.asarray(pts + np.float32(tag) * 0.01)))
            assert pub.action == "refit"
        observed["resolvable"] = store.get("pts", entry.version) is entry
        observed["version"] = entry.version
        return real(engine, config, entry, group)

    monkeypatch.setattr(SRV, "execute_group", racing_execute)
    (resp,) = srv.handle([knn_request(_pts(4, seed=13), 2, "pts")])
    assert observed == {"resolvable": True, "version": 1}
    assert resp.stats.index_version == 1        # served on the pinned snapshot
    assert store._pins == {}                    # balanced after handle()
    with pytest.raises(KeyError):               # released -> evicted
        store.get("pts", 1)
    assert store.get("pts").version == 4


def test_pipeline_background_refit_over_sharded_store():
    pts = _pts(seed=14)
    cfg = PipelineConfig(service=_cfg())
    with ServingPipeline(store=ShardedIndexStore(_mesh1(), "data"),
                         config=cfg) as pipe:
        pipe.create_index("default", pts)
        r1 = pipe.submit(knn_request(_pts(4, seed=15), 2)).result(60.0)
        assert r1.stats.route == "sharded" and r1.stats.index_version == 1
        pipe.update_index("default", G.Points(jnp.asarray(pts + 0.01)))
        assert pipe.wait_maintenance_idle(60.0)
        r2 = pipe.submit(knn_request(_pts(4, seed=16), 2)).result(60.0)
        assert r2.stats.index_version == 2
        st = pipe.stats()
        assert st.refits == 1


# ---------------------------------------------------------------------------
# from_local_trees validation (the loud-error satellite)
# ---------------------------------------------------------------------------

def test_from_local_trees_validates_loudly():
    mesh = _mesh1()
    pts = _pts(seed=17)
    dt = DistributedTree(mesh, "data", pts)

    with pytest.raises(ValueError, match="not an axis"):
        DistributedTree.from_local_trees(mesh, "rows", pts, dt.trees,
                                         dt.top_lo, dt.top_hi)
    with pytest.raises(ValueError, match="leaves"):
        DistributedTree.from_local_trees(mesh, "data", pts[:32], dt.trees,
                                         dt.top_lo, dt.top_hi)
    with pytest.raises(ValueError, match="per-shard scene boxes"):
        DistributedTree.from_local_trees(mesh, "data", pts, dt.trees,
                                         dt.top_lo[:, :1], dt.top_hi[:, :1])
    # trees whose node count disagrees with 2N - R came from a different
    # mesh partitioning (an R-shard build has R fewer internal nodes)
    import dataclasses
    short = dataclasses.replace(dt.trees, node_lo=dt.trees.node_lo[:-1],
                                node_hi=dt.trees.node_hi[:-1])
    with pytest.raises(ValueError, match="different mesh"):
        DistributedTree.from_local_trees(mesh, "data", pts, short,
                                         dt.top_lo, dt.top_hi)

    # the happy path round-trips: wrapped tree answers like the original
    dt2 = DistributedTree.from_local_trees(mesh, "data", pts, dt.trees,
                                           dt.top_lo, dt.top_hi)
    from repro.core import predicates as P
    q = G.Points(jnp.asarray(_pts(4, seed=18)))
    a, b = dt.query(P.nearest(q, k=3)), dt2.query(P.nearest(q, k=3))
    assert np.array_equal(np.asarray(a.indices), np.asarray(b.indices))


# ---------------------------------------------------------------------------
# multi-shard semantics (8 fake host devices, subprocess)
# ---------------------------------------------------------------------------

def test_sharded_serving_matches_single_device_8dev(subproc):
    subproc("""
import numpy as np, jax, jax.numpy as jnp
from repro.compat import make_mesh
from repro.core import geometry as G
from repro.service import (IndexStore, QueryServer, ServiceConfig,
                           ShardedIndexStore, knn_request, ray_request,
                           within_request)
assert jax.device_count() == 8
rng = np.random.default_rng(1)
pts = rng.uniform(0, 1, (256, 3)).astype(np.float32)
cfg = ServiceConfig(capacity=8, min_bucket=8, max_bucket=64)
sharded = QueryServer(store=ShardedIndexStore(make_mesh((8,), ("data",)),
                                              "data"), config=cfg)
sharded.create_index("default", pts)
plain = QueryServer(store=IndexStore(), config=cfg)
plain.create_index("default", G.Points(jnp.asarray(pts)))
qa = rng.uniform(0, 1, (13, 3)).astype(np.float32)
dirs = rng.normal(size=(13, 3)).astype(np.float32)
reqs = [knn_request(qa, 4), within_request(qa, 0.25),
        ray_request(qa, dirs, 2)]
got, want = sharded.handle(list(reqs)), plain.handle(list(reqs))
assert got[0].stats.route == "sharded"
assert np.allclose(got[0].dists, want[0].dists, atol=1e-6)
assert np.array_equal(got[0].idxs, want[0].idxs)
assert np.array_equal(got[1].counts, want[1].counts)
assert got[1].overflow == want[1].overflow
for n, g, w in zip(got[1].counts, got[1].idxs, want[1].idxs):
    if n <= cfg.capacity:        # overflowing rows truncate to different
        assert set(g[g >= 0].tolist()) == set(w[w >= 0].tolist())
assert np.allclose(got[2].dists, want[2].dists, atol=1e-5)
print("OK")
""")


def test_distributed_refit_per_shard_quality_8dev(subproc):
    subproc("""
import numpy as np, jax, jax.numpy as jnp
from repro.compat import make_mesh
from repro.core import geometry as G
from repro.service import ShardedIndexStore
assert jax.device_count() == 8
rng = np.random.default_rng(2)
pts = rng.uniform(0, 1, (256, 3)).astype(np.float32)
store = ShardedIndexStore(make_mesh((8,), ("data",)), "data")
e1 = store.build("pts", pts)
assert len(e1.sah) == 8 and e1.degradation == 1.0
e2 = store.update("pts", G.Points(jnp.asarray(pts + 0.005)))
assert e2.action == "refit" and len(e2.sah) == 8
# wreck ONE shard's locality: worst-rank decides, whole index rebuilds
bad = pts.copy()
bad[:32] = rng.permutation(bad[:32]) * 50
e3 = store.update("pts", G.Points(jnp.asarray(bad)))
assert e3.action == "rebuild", e3.action
print("OK")
""")

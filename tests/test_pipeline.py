"""Async deadline-aware serving pipeline (DESIGN.md §7).

The two headline contracts:
  * batches form adaptively — a group closes when FULL (max_bucket rows)
    or when the tightest deadline budget (minus the per-bucket service
    estimate) is about to be spent;
  * index maintenance runs off the request path — a rebuild completing
    mid-stream publishes via the store's atomic swap while in-flight
    batches finish on their pinned version, and serving never waits on a
    build.
"""
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import geometry as G
from repro.core import predicates as P
from repro.core.access import default_indexable_getter
from repro.core.bvh import BVH
from repro.service import (PipelineConfig, ServiceConfig, ServingPipeline,
                           knn_request, ray_request, within_request)
import repro.service.pipeline as PL

DIM = 3


def _pts(n, seed=0):
    return np.random.default_rng(seed).uniform(
        0, 1, (n, DIM)).astype(np.float32)


def _config(**kw):
    svc = ServiceConfig(capacity=kw.pop("capacity", 8),
                        min_bucket=8, max_bucket=kw.pop("max_bucket", 16))
    return PipelineConfig(service=svc, **kw)


def _pipeline(n=300, seed=1, **kw):
    """n=0 skips the default index (the test creates its own)."""
    pipe = ServingPipeline(config=_config(**kw))
    if n:
        pipe.create_index("default", G.Points(jnp.asarray(_pts(n, seed))))
    return pipe


# ---------------------------------------------------------------------------
# correctness: async results == direct BVH queries
# ---------------------------------------------------------------------------

def test_pipeline_results_match_direct_queries():
    pts = _pts(400, seed=2)
    with _pipeline(0, 0) as pipe:   # replace default index below
        pipe.create_index("default", G.Points(jnp.asarray(pts)))
        bvh = BVH(G.Points(jnp.asarray(pts)))
        qa, qb = _pts(5, 3), _pts(7, 4)
        dirs = np.random.default_rng(5).normal(size=(7, DIM)).astype(np.float32)
        tk = pipe.submit(knn_request(qa, k=3))
        tw = pipe.submit(within_request(qb, 0.2))
        tr = pipe.submit(ray_request(qb, dirs, k=2))
        rk, rw, rr = (t.result(60.0) for t in (tk, tw, tr))

    want = bvh.query(P.nearest(G.Points(jnp.asarray(qa)), k=3))
    assert np.allclose(rk.dists, np.asarray(want.distances), atol=1e-6)
    assert np.array_equal(rk.idxs, np.asarray(want.indices))
    counts = bvh.count(P.intersects(
        G.Spheres(jnp.asarray(qb), jnp.full((7,), 0.2, jnp.float32))))
    assert np.array_equal(rw.counts, np.asarray(counts))
    from repro.core import raytracing as RT
    t, _ = RT.cast_nearest(bvh, G.Rays(jnp.asarray(qb), jnp.asarray(dirs)),
                           k=2)
    assert np.allclose(rr.dists, np.asarray(t), atol=1e-6)
    # timing stats populated on every async response
    for r in (rk, rw, rr):
        assert r.stats.queue_wait_us >= 0 and r.stats.service_us > 0
        assert r.stats.index_version == 1


# ---------------------------------------------------------------------------
# adaptive batch formation
# ---------------------------------------------------------------------------

def test_group_closes_when_full():
    with _pipeline(200, seed=6, max_bucket=16) as pipe:
        # 10s deadlines: only the FULL trigger can close this group fast
        t1 = pipe.submit(knn_request(_pts(8, 7), k=2), deadline_us=10_000_000)
        t2 = pipe.submit(knn_request(_pts(8, 8), k=2), deadline_us=10_000_000)
        r1, r2 = t1.result(60.0), t2.result(60.0)
        st = pipe.stats()
    assert r1.stats.bucket == r2.stats.bucket == 16   # one shared batch
    assert st.batches == 1 and st.closed_full == 1
    assert st.batch_rows == 16 and st.batch_slots == 16


def test_group_closes_on_deadline_budget():
    with _pipeline(200, seed=9, default_service_est_us=30_000.0) as pipe:
        pipe.warmup("default", [("knn", 2)], max_bucket=8)
        t = pipe.submit(knn_request(_pts(1, 10), k=2), deadline_us=100_000)
        r = t.result(60.0)
        st = pipe.stats()
    # it lingered for more traffic (deadline - est - slack ~= 69ms), then
    # the budget forced the close in time to meet the deadline
    assert st.closed_deadline == 1 and st.closed_full == 0
    assert r.stats.queue_wait_us >= 40_000
    assert r.stats.queue_wait_us + r.stats.service_us <= 100_000
    assert not r.stats.deadline_missed
    assert r.stats.deadline_us == 100_000


def test_hopeless_deadline_dispatches_immediately_and_is_flagged():
    with _pipeline(200, seed=11) as pipe:
        t = pipe.submit(knn_request(_pts(1, 12), k=2), deadline_us=1_000)
        r = t.result(60.0)
    # budget < estimate: no point waiting — dispatch now, record the miss
    assert r.stats.queue_wait_us < 1_000_000
    assert r.stats.deadline_missed


def test_no_deadline_rides_linger_cap():
    with _pipeline(200, seed=13, max_linger_us=2_000.0) as pipe:
        t = pipe.submit(knn_request(_pts(2, 14), k=2))
        r = t.result(60.0)
    assert r.stats.deadline_us is None and not r.stats.deadline_missed
    assert r.stats.queue_wait_us < 5_000_000    # did not wait forever


def test_oversized_request_dispatches_alone_at_natural_bucket():
    with _pipeline(200, seed=15, max_bucket=16) as pipe:
        t = pipe.submit(knn_request(_pts(40, 16), k=2), deadline_us=10_000_000)
        r = t.result(60.0)
        st = pipe.stats()
    assert r.stats.bucket == 64 and st.closed_full == 1
    assert np.asarray(r.idxs).shape == (40, 2)


def test_submit_unknown_kind_raises_named_error():
    from repro.service.batcher import Request
    bogus = object.__new__(Request)      # dodge __post_init__ validation
    object.__setattr__(bogus, "kind", "hyperplane")
    object.__setattr__(bogus, "a", _pts(3, 17))
    object.__setattr__(bogus, "b", None)
    object.__setattr__(bogus, "k", 1)
    object.__setattr__(bogus, "index", "default")
    with _pipeline(100, seed=18) as pipe:
        with pytest.raises(ValueError, match=r"hyperplane.*knn.*within.*ray"):
            pipe.submit(bogus)


def test_unknown_index_fails_ticket_not_pipeline():
    with _pipeline(100, seed=19) as pipe:
        t = pipe.submit(knn_request(_pts(2, 20), k=1, index="nope"))
        with pytest.raises(KeyError, match="nope"):
            t.result(60.0)
        # pipeline still serves afterwards
        ok = pipe.submit(knn_request(_pts(2, 21), k=1))
        assert ok.result(60.0).stats.index_version == 1
        assert pipe.stats().failed == 1


def test_close_drains_pending_requests():
    pipe = _pipeline(200, seed=22)
    tickets = [pipe.submit(knn_request(_pts(2, 23 + i), k=2),
                           deadline_us=10_000_000) for i in range(3)]
    pipe.close()
    assert all(t.done() for t in tickets)
    assert {t.result(0).stats.index_version for t in tickets} == {1}
    with pytest.raises(RuntimeError, match="closed"):
        pipe.submit(knn_request(_pts(1, 29), k=2))


# ---------------------------------------------------------------------------
# background maintenance
# ---------------------------------------------------------------------------

def test_serving_never_blocks_on_maintenance():
    """While a rebuild is stuck in its (slow) build phase, traffic keeps
    being served on the pinned previous version; the finished shadow index
    publishes via the atomic swap only when the build completes."""
    gate, in_build = threading.Event(), threading.Event()

    def gated_getter(values):
        # gate ONLY the maintenance thread: the serving path may also call
        # the getter (the bruteforce executable traces through it)
        if "maintenance" in threading.current_thread().name:
            in_build.set()
            assert gate.wait(60.0)
        return default_indexable_getter(values)

    with _pipeline(0, 0) as pipe:
        pipe.create_index("default", G.Points(jnp.asarray(_pts(150, 30))),
                          gated_getter)
        # different leaf count -> forced full rebuild in the worker
        pipe.update_index("default", G.Points(jnp.asarray(_pts(200, 31))))
        assert in_build.wait(60.0)

        # maintenance is mid-build RIGHT NOW; serving must proceed on v1
        served = [pipe.submit(knn_request(_pts(2, 32 + i), k=2)).result(60.0)
                  for i in range(3)]
        assert [r.stats.index_version for r in served] == [1, 1, 1]
        assert pipe.stats().swap_count == 0       # nothing published yet

        gate.set()
        assert pipe.wait_maintenance_idle(60.0)
        st = pipe.stats()
        assert st.swap_count == 1 and st.rebuilds == 1
        assert st.stalled_behind_maintenance == 0
        after = pipe.submit(knn_request(_pts(2, 40), k=2)).result(60.0)
        assert after.stats.index_version == 2


def test_rebuild_publishes_mid_flight_while_batch_finishes_on_pinned_version(
        monkeypatch):
    """The acceptance pin: a full rebuild completing while a batch is in
    flight publishes atomically; the in-flight batch still returns results
    stamped with the version it pinned at dispatch time."""
    real_execute = PL.execute_group
    in_dispatch, go = threading.Event(), threading.Event()
    gating = [True]

    def gated_execute(engine, config, entry, group):
        if gating[0]:
            gating[0] = False
            in_dispatch.set()
            assert go.wait(60.0)
        return real_execute(engine, config, entry, group)

    monkeypatch.setattr(PL, "execute_group", gated_execute)
    pipe = _pipeline(150, seed=41)
    try:
        t = pipe.submit(knn_request(_pts(2, 42), k=2))
        assert in_dispatch.wait(60.0)     # batch pinned v1, now "executing"

        # rebuild (leaf count changes) runs AND publishes during the flight
        pipe.update_index("default", G.Points(jnp.asarray(_pts(220, 43))))
        assert pipe.wait_maintenance_idle(60.0)
        assert pipe.store.get("default").version == 2   # swap happened

        go.set()
        r = t.result(60.0)
        assert r.stats.index_version == 1               # pinned throughout
        r2 = pipe.submit(knn_request(_pts(2, 44), k=2)).result(60.0)
        assert r2.stats.index_version == 2              # next batch: new tree
    finally:
        go.set()
        pipe.close()


def test_updates_coalesce_to_newest_values():
    with _pipeline(0, 0) as pipe:
        gate, in_build = threading.Event(), threading.Event()

        def gated_getter(values):
            if "maintenance" in threading.current_thread().name \
                    and not gate.is_set():
                in_build.set()
                assert gate.wait(60.0)
            return default_indexable_getter(values)

        base = _pts(100, 50)
        pipe.create_index("default", G.Points(jnp.asarray(base)), gated_getter)
        pipe.update_index("default", G.Points(jnp.asarray(_pts(120, 51))))
        assert in_build.wait(60.0)        # worker busy with the first update
        # three more updates queue while it runs; they coalesce to the last
        for n in (130, 140, 160):
            pipe.update_index("default", G.Points(jnp.asarray(_pts(n, n))))
        gate.set()
        assert pipe.wait_maintenance_idle(60.0)
        st = pipe.stats()
        assert pipe.store.get("default").bvh.size() == 160
        assert st.swap_count == 2         # first update + the coalesced one


def test_failed_update_keeps_its_exception_in_stats():
    """A maintenance failure is counted AND kept: stats() carries the
    exception so a caller (chip_smoke.py) can re-raise it, and it is
    visible as soon as wait_maintenance_idle returns."""
    with _pipeline() as pipe:
        boom = RuntimeError("refit exploded")

        def failing_update(name, values):
            raise boom

        pipe.store.update = failing_update
        assert pipe.stats().last_maintenance_error is None
        pipe.update_index("default", G.Points(jnp.asarray(_pts(300, 9))))
        assert pipe.wait_maintenance_idle(60.0)
        st = pipe.stats()
        assert st.maintenance_errors == 1
        assert st.last_maintenance_error is boom
        assert st.swap_count == 0
        # serving is unaffected: the live version is still the build
        r = pipe.submit(knn_request(_pts(2, 10), k=2)).result(60.0)
        assert r.stats.index_version == 1

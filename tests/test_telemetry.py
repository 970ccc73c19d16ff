"""Unified telemetry subsystem (ISSUE 9, DESIGN.md §10).

The four contracts under test:
  * the tracer is bounded, thread-safe, nests per thread, and records on
    exception exits; ``fence`` stamps device-clocked durations;
  * the metrics registry's gauge high-water mark updates atomically with
    the level — the queue-depth race class is gone by construction;
  * the exporters round-trip (emit -> write -> parse -> validate) and
    the validators actually reject malformed payloads;
  * the serving pipeline's per-request phase spans TILE the recorded
    latency (sum == queue_wait_us + service_us), and a DISABLED tracer
    costs zero recompiles and under 1% of serving wall time.
"""
import json
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.core import geometry as G
from repro.service import (PipelineConfig, ServiceConfig, ServingPipeline,
                           QueryServer, knn_request, ray_request,
                           within_request)
from repro.service.pipeline import REQUEST_PHASES
import repro.service.pipeline as PL
from repro.telemetry import (MetricsRegistry, Tracer, read_metrics_jsonl,
                             summarize_spans, validate_chrome_trace,
                             validate_metrics_lines, write_chrome_trace,
                             write_metrics_jsonl)

DIM = 3


def _pts(n, seed=0):
    return np.random.default_rng(seed).uniform(
        0, 1, (n, DIM)).astype(np.float32)


@pytest.fixture
def fresh_tracer():
    """Enable telemetry on a fresh ring; restore the disabled default."""
    was = telemetry.enabled()
    tracer = telemetry.enable(capacity=65536)
    yield tracer
    if not was:
        telemetry.disable()
    tracer.drain()


@pytest.fixture
def telemetry_disabled():
    was = telemetry.enabled()
    telemetry.disable()
    yield
    if was:
        telemetry.enable()


def _pipeline(n=300, seed=1, **kw):
    svc = ServiceConfig(capacity=kw.pop("capacity", 8), min_bucket=8,
                        max_bucket=kw.pop("max_bucket", 16))
    pipe = ServingPipeline(config=PipelineConfig(service=svc, **kw))
    if n:
        pipe.create_index("default", G.Points(jnp.asarray(_pts(n, seed))))
    return pipe


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_ring_buffer_bounds_memory_oldest_spans_fall_off():
    tr = Tracer(capacity=16)
    for i in range(50):
        with tr.span(f"s{i}"):
            pass
    spans = tr.spans()
    assert len(spans) == len(tr) == 16
    assert [s.name for s in spans] == [f"s{i}" for i in range(34, 50)]
    assert tr.drain() == spans and len(tr) == 0     # drain clears


def test_nested_spans_carry_parent_ids():
    tr = Tracer()
    with tr.span("outer", op="knn") as outer:
        with tr.span("inner") as inner:
            pass
        with tr.span("inner2"):
            pass
    by_name = {s.name: s for s in tr.drain()}
    assert by_name["outer"].parent_id == 0
    assert by_name["inner"].parent_id == outer.span_id
    assert by_name["inner2"].parent_id == outer.span_id
    assert by_name["inner"].span_id == inner.span_id != outer.span_id
    assert by_name["outer"].args == {"op": "knn"}


def test_span_stacks_are_per_thread():
    """A span opened on another thread must NOT parent under the span
    currently open on this one (scheduler vs maintenance threads)."""
    tr = Tracer()

    def worker():
        with tr.span("other"):
            pass

    with tr.span("main-root"):
        th = threading.Thread(target=worker, name="tel-worker")
        th.start()
        th.join()
    by_name = {s.name: s for s in tr.drain()}
    assert by_name["other"].parent_id == 0
    assert by_name["other"].tid == "tel-worker"
    assert by_name["main-root"].tid == threading.current_thread().name


def test_exception_exit_still_records_with_error_arg():
    tr = Tracer()
    with pytest.raises(RuntimeError, match="boom"):
        with tr.span("failing", stage=1):
            raise RuntimeError("boom")
    (s,) = tr.drain()
    assert s.name == "failing"
    assert s.args == {"stage": 1, "error": "RuntimeError"}


def test_fence_stamps_device_clock_and_dur_us():
    tr = Tracer()
    with tr.span("kernel") as sp:
        out = sp.fence(jnp.arange(8).sum())
    assert int(out) == 28                     # fence returns its value
    (s,) = tr.drain()
    assert s.clock == "device"
    assert s.dur_ns > 0
    assert sp.dur_us == pytest.approx(s.dur_ns / 1e3)


def test_annotate_merges_args():
    tr = Tracer()
    with tr.span("sp", a=1) as sp:
        sp.annotate(b=2).annotate(a=3)
    (s,) = tr.drain()
    assert s.args == {"a": 3, "b": 2}


def test_add_span_records_retroactive_intervals():
    tr = Tracer()
    root = tr.add_span("request", 1_000, 5_000, tid="requests", kind="knn")
    kid = tr.add_span("request.kernel", 1_000, 2_500, parent_id=root,
                      clock="device")
    neg = tr.add_span("negative", 10, 5)      # clamps, never negative
    a, b, c = tr.drain()
    assert (a.span_id, a.t0_ns, a.dur_ns, a.tid) == (root, 1_000, 4_000,
                                                     "requests")
    assert (b.span_id, b.parent_id, b.clock) == (kid, root, "device")
    assert (c.span_id, c.dur_ns) == (neg, 0)
    assert root != kid != neg


def test_disabled_module_span_is_the_shared_noop(telemetry_disabled):
    sp = telemetry.span("anything", a=1)
    with sp:
        pass
    assert sp is telemetry.NULL_SPAN
    assert sp is telemetry.span("something-else")
    assert sp.span_id == 0 and sp.dur_us == 0.0
    obj = object()
    assert sp.fence(obj) is obj               # passthrough: no device sync
    assert sp.annotate(z=2) is sp
    telemetry.get_tracer().drain()
    with telemetry.span("never-recorded"):
        pass
    assert len(telemetry.get_tracer()) == 0


def test_enable_disable_toggles_and_swaps_rings():
    was = telemetry.enabled()
    try:
        t1 = telemetry.enable(capacity=8)
        assert telemetry.enabled() and telemetry.get_tracer() is t1
        with telemetry.span("live"):
            pass
        assert [s.name for s in t1.drain()] == ["live"]
        t2 = telemetry.enable(capacity=4)     # fresh ring
        assert t2 is not t1 and telemetry.get_tracer() is t2
        telemetry.disable()
        assert not telemetry.enabled()
        assert telemetry.get_tracer() is t2   # tracer survives disable
    finally:
        telemetry.disable()
        if was:
            telemetry.enable()


# ---------------------------------------------------------------------------
# the profiler sink and phase runs
# ---------------------------------------------------------------------------

@pytest.fixture
def profiler(tmp_path):
    """Record a JAX profiler trace into tmp_path; the returned function
    stops it and gives the host events as {name: [stats dict, ...]}."""
    import glob
    import os

    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    running = [True]

    def stop():
        jax.profiler.stop_trace()
        running[0] = False
        (path,) = glob.glob(os.path.join(str(tmp_path), "plugins",
                                         "profile", "*", "*.xplane.pb"))
        events: dict = {}
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        events.setdefault(e.name, []).append(
                            {k: v for k, v in e.stats})
        return events

    yield stop
    if running[0]:
        jax.profiler.stop_trace()


def test_profiled_span_lands_on_a_host_plane_with_its_args(
        telemetry_disabled, profiler):
    with telemetry.span("probe.profiled", batch=7, kind="knn") as sp:
        sp.annotate(bucket=64)
    assert len(telemetry.get_tracer()) == 0     # the ring stays off
    (stats,) = profiler()["probe.profiled"]
    assert stats == {"batch": 7, "kind": "knn", "bucket": 64}


def test_profiled_span_never_fences(telemetry_disabled, profiler,
                                    monkeypatch):
    import jax
    calls = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: calls.append(x) or real(x))
    value = jnp.arange(8) * 2
    with telemetry.span("probe.fence") as sp:
        out = sp.fence(value)
    assert out is value and calls == []
    assert sp.span_id == 0 and sp.dur_us == 0.0
    assert "probe.fence" in profiler()


def test_span_with_both_sinks_is_in_the_ring_and_the_profile(
        fresh_tracer, profiler):
    with telemetry.span("probe.both", op="knn") as sp:
        sp.fence(jnp.ones(4).sum())
    (s,) = [s for s in fresh_tracer.drain() if s.name == "probe.both"]
    assert s.clock == "device" and s.args == {"op": "knn"}
    assert profiler()["probe.both"] == [{"op": "knn"}]


def test_span_with_both_sinks_off_is_the_null_span(telemetry_disabled):
    from jax.profiler import TraceAnnotation
    assert not TraceAnnotation.is_enabled()
    assert telemetry.span("probe.off", a=1) is telemetry.NULL_SPAN
    assert telemetry.phase("dispatch") is telemetry.NULL_SPAN
    with telemetry.phases("probe", batch=1):
        assert telemetry.phase("dispatch") is telemetry.NULL_SPAN
    assert len(telemetry.get_tracer()) == 0


def test_phases_tile_a_thread_with_flat_spans(fresh_tracer):
    with telemetry.phases("lane", batch=3):
        telemetry.phase("a", x=1)
        with telemetry.span("child"):
            pass
        telemetry.phase("b", y=2)
        telemetry.phase("c")
    assert telemetry.phase("d") is telemetry.NULL_SPAN   # block closed
    spans = fresh_tracer.drain()
    lane = [s for s in spans if s.name.startswith("lane.")]
    assert [s.name for s in lane] == ["lane.a", "lane.b", "lane.c"]
    assert all(s.parent_id == 0 and s.args["batch"] == 3 for s in lane)
    assert lane[0].args["x"] == 1 and lane[1].args["y"] == 2
    for prev, nxt in zip(lane, lane[1:]):
        assert prev.t0_ns + prev.dur_ns <= nxt.t0_ns
    (child,) = [s for s in spans if s.name == "child"]
    assert child.parent_id == lane[0].span_id


def test_scheduler_phases_are_flat_and_tile_each_batch(fresh_tracer):
    with _pipeline(200, seed=72) as pipe:
        for i in range(3):
            pipe.submit(knn_request(_pts(3, 73 + i), k=2),
                        deadline_us=1_000.0).result(60.0)
    spans = fresh_tracer.drain()
    names = ("pipeline.dispatch", "pipeline.device_wait",
             "pipeline.copy_back", "pipeline.deliver")
    sched = sorted((s for s in spans if s.name in names
                    or s.name == "pipeline.wait"), key=lambda s: s.t0_ns)
    assert all(s.parent_id == 0 for s in sched)
    for prev, nxt in zip(sched, sched[1:]):
        assert prev.t0_ns + prev.dur_ns <= nxt.t0_ns
    batches = [s for s in sched if s.name != "pipeline.wait"]
    assert [s.name for s in batches] == list(names) * 3
    assert [s.args["batch"] for s in batches] == [1] * 4 + [2] * 4 + [3] * 4
    by_id = {s.span_id: s for s in spans}

    def phase_of(s):
        while s.parent_id:
            s = by_id[s.parent_id]
        return s.name

    for name, phase in (("server.execute_group", "pipeline.dispatch"),
                        ("engine.kernel", "pipeline.dispatch"),
                        ("server.assemble", "pipeline.copy_back"),
                        ("server.scatter", "pipeline.deliver")):
        kids = [s for s in spans if s.name == name
                and s.tid == "repro-pipeline-scheduler"]
        assert len(kids) == 3
        assert all(phase_of(s) == phase for s in kids), name


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_counter_concurrent_adds_do_not_lose_increments():
    reg = MetricsRegistry()
    c = reg.counter("hits")

    def work():
        for _ in range(1000):
            c.add(1)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8000


def test_gauge_high_water_updates_atomically_with_the_level():
    """8 threads x 1000 increments: the high-water mark must equal the
    final level exactly — the old caller-side read-modify-write max could
    under-report a peak two threads built together."""
    reg = MetricsRegistry()
    g = reg.gauge("depth")

    def work():
        for _ in range(1000):
            g.adjust(+1)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert g.value == 8000 and g.high == 8000


def test_gauge_high_water_survives_drains():
    reg = MetricsRegistry()
    g = reg.gauge("depth")

    def churn():
        for _ in range(500):
            g.adjust(+1)
            g.adjust(-1)

    threads = [threading.Thread(target=churn) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert g.value == 0
    assert 1 <= g.high <= 8                   # never above true concurrency
    g.note_high(3)                            # can only EXTEND
    high = g.high
    g.note_high(high - 1)
    assert g.high == high
    assert g.to_dict() == {"type": "gauge", "value": 0, "high": high}


def test_registry_get_or_create_and_type_collision():
    reg = MetricsRegistry()
    assert reg.counter("x") is reg.counter("x")
    reg.gauge("g")
    with pytest.raises(TypeError, match="already registered"):
        reg.counter("g")
    assert reg.names() == ["g", "x"]
    snap = reg.snapshot()
    assert snap["x"]["type"] == "counter"
    assert snap["g"]["type"] == "gauge"


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def _demo_spans():
    tr = Tracer()
    with tr.span("outer", op="knn") as outer:
        with tr.span("inner") as sp:
            sp.fence(jnp.zeros(4))
    tr.add_span("retro", outer._t0, outer._t0 + 2_000, parent_id=outer.span_id,
                tid="requests")
    return tr.drain()


def test_chrome_trace_round_trip(tmp_path):
    spans = _demo_spans()
    path = str(tmp_path / "trace.json")
    write_chrome_trace(path, spans, metadata={"suite": "unit"})
    with open(path) as fh:
        obj = json.load(fh)
    assert validate_chrome_trace(obj) == []
    assert obj["otherData"] == {"suite": "unit"}
    xs = [ev for ev in obj["traceEvents"] if ev["ph"] == "X"]
    ms = [ev for ev in obj["traceEvents"] if ev["ph"] == "M"]
    assert len(xs) == 3
    assert min(ev["ts"] for ev in xs) == 0    # relative to the trace epoch
    by_name = {ev["name"]: ev for ev in xs}
    assert by_name["inner"]["args"]["parent_id"] \
        == by_name["outer"]["args"]["span_id"]
    assert by_name["inner"]["args"]["clock"] == "device"
    assert by_name["outer"]["args"]["op"] == "knn"
    # one thread_name metadata event per distinct thread, names preserved
    assert {ev["args"]["name"] for ev in ms} \
        == {threading.current_thread().name, "requests"}
    assert {ev["tid"] for ev in ms} == {ev["tid"] for ev in xs}


def test_validate_chrome_trace_rejects_malformed():
    assert validate_chrome_trace([]) != []
    assert validate_chrome_trace({"events": []}) != []
    assert validate_chrome_trace({"traceEvents": {}}) != []
    ok = {"name": "a", "ph": "X", "ts": 0, "dur": 1.0, "pid": 0, "tid": 0}
    assert validate_chrome_trace({"traceEvents": [ok]}) == []
    bad_ph = dict(ok, ph="B")
    assert any("ph=" in p for p in
               validate_chrome_trace({"traceEvents": [bad_ph]}))
    neg_ts = dict(ok, ts=-5)
    assert any("ts=" in p for p in
               validate_chrome_trace({"traceEvents": [neg_ts]}))
    missing = {"ph": "X", "ts": 0, "dur": 1, "pid": 0}
    problems = validate_chrome_trace({"traceEvents": [missing]})
    assert any("'name'" in p for p in problems)
    assert any("'tid'" in p for p in problems)
    meta = {"name": "thread_name", "ph": "M", "pid": 0, "tid": 0,
            "args": {"name": "x"}}
    assert validate_chrome_trace({"traceEvents": [ok, meta]}) == []


def test_metrics_jsonl_round_trip(tmp_path):
    reg = MetricsRegistry()
    reg.counter("c").add(3)
    g = reg.gauge("g")
    g.adjust(+2)
    g.adjust(-1)
    path = str(tmp_path / "metrics.jsonl")
    assert write_metrics_jsonl(path, reg) == 2
    back = read_metrics_jsonl(path)
    assert validate_metrics_lines(back) == []
    assert back["c"]["value"] == 3
    assert (back["g"]["value"], back["g"]["high"]) == (1, 2)


def test_validate_metrics_rejects_malformed():
    assert validate_metrics_lines({"x": {"type": "counter"}}) != []
    assert validate_metrics_lines({"x": {"type": "gauge", "value": 1}}) != []
    assert validate_metrics_lines({"x": {"type": "nope"}}) != []
    assert validate_metrics_lines(
        {"c": {"type": "counter", "value": 2},
         "g": {"type": "gauge", "value": 1, "high": 3}}) == []


def test_summarize_spans_aggregates_per_name():
    tr = Tracer()
    tr.add_span("a", 0, 2_000)
    tr.add_span("a", 0, 4_000)
    tr.add_span("b", 0, 1_000)
    summary = summarize_spans(tr.drain())
    assert summary == {
        "a": {"count": 2, "total_us": 6.0, "max_us": 4.0},
        "b": {"count": 1, "total_us": 1.0, "max_us": 1.0},
    }


def test_report_selftest_round_trips():
    from repro.telemetry import report
    assert report.selftest() == 0
    assert report.main(["--selftest"]) == 0
    assert report.main([]) == 2               # usage error
    assert report.main(["/nonexistent/trace.json"]) == 1


# ---------------------------------------------------------------------------
# pipeline phase attribution (the acceptance property)
# ---------------------------------------------------------------------------

def test_request_phase_spans_tile_recorded_latency(fresh_tracer):
    """Every response's phase_us dict sums EXACTLY to queue_wait_us +
    service_us, and the synthesized span tree (one "request" root + five
    phase children found via RequestStats.span_id) agrees within the 5%
    acceptance tolerance — including for deadline-missed requests."""
    with _pipeline(200, seed=70) as pipe:
        # hopeless deadlines: guaranteed misses exercise the flagged path
        tickets = [pipe.submit(knn_request(_pts(2, 71 + i), k=2),
                               deadline_us=1_000.0) for i in range(6)]
        responses = [t.result(60.0) for t in tickets]
    spans = fresh_tracer.drain()
    assert any(r.stats.deadline_missed for r in responses)

    for r in responses:
        st = r.stats
        assert st.span_id > 0
        assert set(st.phase_us) == set(REQUEST_PHASES)
        assert all(v >= 0 for v in st.phase_us.values())
        expect = st.queue_wait_us + st.service_us
        assert sum(st.phase_us.values()) == pytest.approx(expect, rel=1e-6)
        assert st.phase_us["kernel"] == pytest.approx(st.kernel_us)

        (root,) = [s for s in spans if s.span_id == st.span_id]
        assert root.name == "request" and root.tid == "requests"
        assert root.args["deadline_missed"] == st.deadline_missed
        kids = [s for s in spans if s.parent_id == st.span_id]
        assert {s.name for s in kids} \
            == {f"request.{p}" for p in REQUEST_PHASES}
        child_sum_us = sum(s.dur_ns for s in kids) / 1e3
        assert abs(child_sum_us - expect) <= 0.05 * expect
        (kern,) = [s for s in kids if s.name == "request.kernel"]
        assert kern.clock == "device"


def test_serving_span_taxonomy_reaches_the_kernel(fresh_tracer):
    with _pipeline(200, seed=75) as pipe:
        r = pipe.submit(knn_request(_pts(3, 76), k=2),
                        deadline_us=1_000.0).result(60.0)
    assert r.stats.kernel_us > 0
    names = {s.name for s in fresh_tracer.drain()}
    for expected in ("pipeline.submit", "pipeline.dispatch",
                     "server.execute_group", "server.assemble",
                     "server.scatter", "engine.kernel",
                     "store.build", "request", "request.kernel"):
        assert expected in names, f"missing span {expected!r}"


def test_maintenance_spans_cover_refit_and_swap(fresh_tracer):
    with _pipeline(0, 0) as pipe:
        pts = _pts(150, 77)
        pipe.create_index("default", G.Points(jnp.asarray(pts)))
        pipe.update_index("default", G.Points(jnp.asarray(pts + 0.001)))
        assert pipe.wait_maintenance_idle(60.0)
    names = {s.name for s in fresh_tracer.drain()}
    for expected in ("pipeline.maintenance", "store.refit", "store.swap"):
        assert expected in names, f"missing span {expected!r}"


def test_pipeline_metrics_registry_exports_jsonl(tmp_path):
    """The README workflow: pipeline stats flow into the JSONL dump via
    the public metrics_registry accessor."""
    with _pipeline(150, seed=78) as pipe:
        pipe.submit(knn_request(_pts(2, 79), k=2),
                    deadline_us=1_000.0).result(60.0)
        reg = pipe.metrics_registry
        path = str(tmp_path / "pipeline.jsonl")
        assert write_metrics_jsonl(path, reg) > 0
    back = read_metrics_jsonl(path)
    assert validate_metrics_lines(back) == []
    assert back["pipeline.served"]["value"] == 1
    assert back["pipeline.queue_depth"]["high"] >= 1


def test_queue_depth_high_water_regression(monkeypatch):
    """Requests queueing while a dispatch is in flight must register in
    max_queue_depth — the mark lives inside the gauge now, so the peak
    cannot be lost between the level write and a separate max update."""
    real_execute = PL.execute_group
    in_dispatch, go = threading.Event(), threading.Event()

    def gated_execute(engine, config, entry, group):
        in_dispatch.set()
        assert go.wait(60.0)
        return real_execute(engine, config, entry, group)

    monkeypatch.setattr(PL, "execute_group", gated_execute)
    pipe = _pipeline(150, seed=80)
    try:
        # hopeless deadline -> dispatches alone immediately, then blocks
        first = pipe.submit(knn_request(_pts(1, 81), k=2),
                            deadline_us=1_000.0)
        assert in_dispatch.wait(60.0)
        backlog = [pipe.submit(knn_request(_pts(1, 82 + i), k=2),
                               deadline_us=10_000_000.0) for i in range(6)]
        st = pipe.stats()
        assert st.queue_depth == 6
        assert st.max_queue_depth >= 6
    finally:
        go.set()
        pipe.close()
    final = pipe.stats()
    assert final.queue_depth == 0             # everything drained
    assert final.max_queue_depth >= 6         # ... but the peak is kept
    assert first.done() and all(t.done() for t in backlog)


# ---------------------------------------------------------------------------
# disabled-tracer overhead (satellite 3)
# ---------------------------------------------------------------------------

def test_disabled_tracer_zero_recompiles_and_sub_percent_overhead(
        telemetry_disabled):
    """With telemetry OFF, the instrumented serving path must (a) keep the
    zero-recompiles-after-warmup contract — nothing telemetry does is
    visible to jit — and (b) cost under 1% of serving wall time for the
    ~10 span sites a request crosses (priced as 1000 no-op spans)."""
    rng = np.random.default_rng(90)
    srv = QueryServer(config=ServiceConfig(capacity=16))
    srv.create_index("default", G.Points(jnp.asarray(_pts(500, 90))))
    srv.warmup("default", [("knn", 8), ("within", 0), ("ray", 1)],
               max_bucket=128, dim=DIM)
    before = srv.engine.stats.snapshot()

    t0 = time.perf_counter()
    served = 0
    for _ in range(25):                       # 25 calls x 4 requests = 100
        m = [int(rng.integers(1, 65)) for _ in range(4)]
        reqs = [knn_request(rng.uniform(0, 1, (m[0], DIM)), k=8),
                within_request(rng.uniform(0, 1, (m[1], DIM)), 0.1),
                knn_request(rng.uniform(0, 1, (m[2], DIM)), k=8),
                ray_request(rng.uniform(0, 1, (m[3], DIM)),
                            rng.normal(size=(m[3], DIM)))]
        served += len(srv.handle(reqs))
    wall = time.perf_counter() - t0
    assert served == 100

    after = srv.engine.stats
    assert after.jit_traces == before.jit_traces       # ZERO recompiles
    assert after.cache_misses == before.cache_misses

    t0 = time.perf_counter()
    for i in range(1000):
        with telemetry.span("overhead.probe", route="pallas", op="knn"):
            pass
    cost = time.perf_counter() - t0
    assert cost < 0.01 * wall, \
        f"1000 disabled spans cost {cost * 1e6:.0f}us " \
        f"({100 * cost / wall:.2f}% of {wall * 1e3:.0f}ms serving wall)"


def test_enabling_telemetry_causes_no_recompiles(fresh_tracer):
    """Toggling tracing on a warm server must not perturb the executable
    cache: spans wrap the launches, they never enter the traced body."""
    srv = QueryServer(config=ServiceConfig(capacity=16))
    srv.create_index("default", G.Points(jnp.asarray(_pts(300, 91))))
    srv.warmup("default", [("knn", 4)], max_bucket=8, dim=DIM)
    before = srv.engine.stats.snapshot()
    fresh_tracer.drain()
    r = srv.handle([knn_request(_pts(3, 92), k=4)])[0]
    assert r.stats.cache_hit
    assert srv.engine.stats.jit_traces == before.jit_traces
    kernels = [s for s in fresh_tracer.drain() if s.name == "engine.kernel"]
    assert kernels and all(s.clock == "device" for s in kernels)
    assert r.stats.kernel_us == pytest.approx(kernels[-1].dur_ns / 1e3)

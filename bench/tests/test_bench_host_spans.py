"""The scheduler's spans in the profiler's trace: the three readers of
``pipeline.dispatch``, ``pipeline.copy_back`` and ``pipeline.deliver``
on recorded events, the idle gaps they name, and on four CPU devices (in
a child process) the sharded pipeline's batches tiled by flat spans."""
import json
import os
import subprocess
import sys

import pytest

from bench import spec
from bench.tests.conftest import ROOT, SRC
from bench.trace import Device, Event, Trace

READERS = {"dispatch_host_ms": "pipeline.dispatch",
           "copy_back_ms": "pipeline.copy_back",
           "deliver_ms": "pipeline.deliver"}
FLAT = ("pipeline.dispatch", "pipeline.device_wait", "pipeline.copy_back",
        "pipeline.deliver")


class _Run:
    def __init__(self, trace):
        self.trace = trace


def _batches(n_batches, durs):
    """`n_batches` batches of flat scheduler spans, one after another,
    with durations `durs` (ns, one per name of FLAT), all on a line
    called ``python3``; the device runs during ``pipeline.device_wait``
    and idles in the rest."""
    host, modules, t = [], [], 0.0
    for _ in range(n_batches):
        for name, dur in zip(FLAT, durs):
            host.append(("python3", Event(name, t, dur)))
            if name == "pipeline.device_wait":
                modules.append(Event("jit_local_fn(1)", t, dur))
            t += dur
    host.append(("python3", Event("whole thread", 0.0, 1e9)))
    return Trace({"/device:TPU:0": Device(modules, {})}, host,
                 window_s=t * 1e-9)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_span_reader_gives_the_mean_per_batch(metric):
    read = spec.load_reader(ROOT, metric)
    durs = (1_500_000.0, 50_000_000.0, 800_000.0, 300_000.0)
    tr = _batches(3, durs)
    # one batch's span lasts twice as long: the mean moves by a third
    k = FLAT.index(READERS[metric])
    i = next(i for i, (_, e) in enumerate(tr.host) if e.name == FLAT[k])
    e = tr.host[i][1]
    tr.host[i] = ("python3", Event(e.name, e.start_ns, 2 * e.dur_ns))
    assert read(_Run(tr)) == pytest.approx(durs[k] * 4 / 3 * 1e-6)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_span_reader_reports_nothing_without_its_spans(metric):
    read = spec.load_reader(ROOT, metric)
    others = [("python3", Event(n, 0.0, 10.0)) for n in FLAT
              if n != READERS[metric]]
    assert read(_Run(Trace({}, others, 1.0))) is None
    assert read(_Run(Trace({}, [], 1.0))) is None
    assert read(_Run(None)) is None


def test_idle_gaps_are_named_by_the_flat_scheduler_span():
    tr = _batches(2, (2_000_000.0, 5_000_000.0, 3_000_000.0, 1_000_000.0))
    # the device idles from the end of one device_wait to the start of
    # the next: copy_back (3 ms), deliver (1 ms), dispatch (2 ms)
    (gap,) = tr.idle_gaps(5)
    assert gap == ["python3: pipeline.copy_back", pytest.approx(6e-3)]


CODE = r"""
import json, os, sys, tempfile, time
import jax
import numpy as np
from bench import trace as TR
from bench.tests.faults import run_tiny
from repro.compat import make_mesh
from repro.service import (PipelineConfig, ServiceConfig, ServingPipeline,
                           ShardedIndexStore, within_request)
from repro.service.server import execute_group, finish_group, launch_group

SCHEDULER = ("pipeline.wait", "pipeline.dispatch", "pipeline.device_wait",
             "pipeline.copy_back", "pipeline.deliver")
out = {}
mesh = make_mesh((4,), ("data",))
svc = ServiceConfig(capacity=16, min_bucket=8, max_bucket=64)
pts = np.random.default_rng(0).uniform(0, 1, (2048, 3)).astype(np.float32)
queries = [np.random.default_rng(1 + i).uniform(0, 1, (64, 3)).astype(
    np.float32) for i in range(5)]
pipe = ServingPipeline(store=ShardedIndexStore(mesh, "data"),
                       config=PipelineConfig(service=svc))
pipe.create_index("cloud", pts)
pipe.submit(within_request(queries[0], 0.05, "cloud")).result(300)

log_dir = tempfile.mkdtemp()
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
b0 = pipe.stats().batches
jax.profiler.start_trace(log_dir, profiler_options=opts)
t0 = time.perf_counter()
for q in queries[1:]:
    pipe.submit(within_request(q, 0.05, "cloud")).result(300)
time.sleep(0.05)
jax.profiler.stop_trace()
tr = TR.load(TR.find_xplane(log_dir), time.perf_counter() - t0)
out["batches"] = pipe.stats().batches - b0
out["spans"] = sorted([e.name, e.start_ns, e.end_ns] for _, e in tr.host
                      if e.name in SCHEDULER)

# execute_group is launch + finish
group = pipe.batcher.plan([within_request(queries[1], 0.05, "cloud")])[0]
entry = pipe.store.pin("cloud")
a = execute_group(pipe.engine, svc, entry, group)
b = finish_group(launch_group(pipe.engine, svc, entry, group))
pipe.store.release(entry)
pipe.close()
out["same"] = (a.keys() == b.keys() and all(
    np.array_equal(a[r].counts, b[r].counts)
    and np.array_equal(a[r].idxs, b[r].idxs)
    and a[r].overflow == b[r].overflow and a[r].stats == b[r].stats
    for r in a))

# the harness's traced run reads the three metrics off the same spans
res = run_tiny("box-16M-4chip.within-bulk", None, points=2048,
               trace=True).result
out["metrics"] = {k: v["value"] for k, v in res["metrics"].items()}
print(json.dumps(out))
"""


def test_sharded_pipeline_batches_are_tiled_by_flat_spans():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([ROOT, SRC]))
    r = subprocess.run([sys.executable, "-c", CODE], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    got = json.loads(r.stdout.strip().splitlines()[-1])
    assert got["same"] is True
    spans = got["spans"]
    batches = got["batches"]
    assert batches == 4
    for name in FLAT:
        assert sum(s[0] == name for s in spans) == batches, name
    # no two top-level scheduler spans overlap, and each batch runs its
    # spans in order
    ordered = sorted(spans, key=lambda s: s[1])
    for prev, nxt in zip(ordered, ordered[1:]):
        assert prev[2] <= nxt[1], (prev, nxt)
    flat = [s[0] for s in ordered if s[0] != "pipeline.wait"]
    assert flat == list(FLAT) * batches
    # CPU devices have no device plane: only the host spans' metrics read
    for metric in READERS:
        assert got["metrics"][metric] > 0, got["metrics"]

"""The result line: its keys and where the numbers compared go, and no
result at all without a chip or without the library."""
import dataclasses
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import spec
from bench.tests.conftest import ROOT, SRC, cell_of, tiny

E2E = ("setup_s", "p95_ms", "publish_ms")
LAYER = ("queue_wait_ms.p95", "occupancy", "client_late_ms.p99",
         "within_device_us_per_query", "refit_device_ms", "idle_share.open")


def _common(res):
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    dev = res["device"]
    assert dev["platform"] == "cpu" and dev["count"] == 1
    assert isinstance(dev["memory_peak_bytes"], int)
    for name, c in res["checks"].items():
        assert set(c) == {"value", "limit"}, name
    json.dumps(res)


def _open_cell():
    """The open-loop cell at a CPU size, with its metrics named here."""
    cell = tiny(cell_of("box-4M.within-open"))
    e2e = tuple(spec.Metric(n, "x", "lower", "host_clock") for n in E2E)
    layer = tuple(spec.Metric(n, "x", "lower", "device_trace", "p95_ms",
                              reader=spec.load_reader(ROOT, n))
                  for n in LAYER)
    return dataclasses.replace(cell, end_to_end=e2e, per_layer=layer)


def _run(trace):
    import time

    import jax

    from bench import run
    return run.run_cell(_open_cell(), jax.devices()[:1], 5, 1.0, trace,
                        time.perf_counter()).result


@pytest.mark.usefixtures("shared_engine")
def test_untraced_run_reports_the_end_to_end_metrics():
    res = _run(trace=False)
    _common(res)
    assert set(res["metrics"]) == set(E2E)
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0


@pytest.mark.usefixtures("shared_engine")
def test_traced_run_reports_the_per_layer_metrics():
    res = _run(trace=True)
    _common(res)
    # the CPU has no device plane: only the counters' metrics are read
    assert set(res["metrics"]) == {"queue_wait_ms.p95", "occupancy",
                                   "client_late_ms.p99"}
    assert 0 < res["metrics"]["occupancy"]["value"] <= 100
    assert res["device"]["busy_s"] == 0.0 and res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def _bench(cwd, env):
    return subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload",
         "box-16M-4chip.within-bulk",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_result_without_a_chip():
    r = _bench(ROOT, dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 2 and r.stdout == ""
    assert "needs a TPU" in r.stderr


def test_no_result_without_the_library(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in env.get("PYTHONPATH", "").split(os.pathsep)
        if p and os.path.abspath(p) != SRC)
    r = _bench(str(tmp_path), env)
    assert r.returncode != 0 and r.stdout == ""

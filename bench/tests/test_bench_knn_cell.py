"""The one-chip kNN cell ``box-4M.knn-bulk``: its metrics as BENCHMARK.json
lists them, the two readers of the kNN loop's counters, and a traced run
on the CPU that reads them from the pipeline."""
import dataclasses
import functools
import time

import pytest

from bench import spec
from bench.run import Run
from bench.tests.conftest import ROOT, tiny

CELL = "box-4M.knn-bulk"


@dataclasses.dataclass
class Snap:
    knn_rows: int = 0
    knn_steps: int = 0
    knn_lane_steps: int = 0


class Bare:
    """A snapshot of a pipeline that has no kNN counters."""


def _run(s0, s1):
    return Run(None, None, None, s0, s1, None)


def _reader(name):
    return spec.load_reader(ROOT, name)


def test_cell_lists_its_metrics():
    cell = spec.load_cell(CELL)
    assert cell.chips == 1 and cell.config["points"] == 1 << 22
    assert cell.traffic["kind"] == "knn" and cell.traffic["rows"] == 1024
    assert [m.name for m in cell.end_to_end] == ["setup_s", "qps"]
    assert sorted(m.name for m in cell.per_layer) == sorted([
        "idle_share.bulk", "knn_device_ms", "knn_steps_per_query",
        "knn_lane_use"])
    for m in cell.per_layer:
        assert m.moves == "qps" and callable(m.reader)


def test_the_four_chip_cell_keeps_its_metrics():
    cell = spec.load_cell("box-16M-4chip.within-bulk")
    assert [m.name for m in cell.end_to_end] == ["setup_s", "qps"]
    assert [m.name for m in cell.per_layer] == [
        "local_traverse_ms", "collective_ms", "idle_share.bulk",
        "dispatch_host_ms", "copy_back_ms", "deliver_ms"]


@pytest.mark.parametrize("s0,s1,steps_per_query,lane_use", [
    (Snap(), Snap(1024, 250_000, 512_000), 250_000 / 1024,
     250_000 / 512_000 * 100),
    (Snap(2048, 100, 400), Snap(4096, 1100, 4400), 1000 / 2048, 25.0),
    # no kNN row served by the loop in the window
    (Snap(5, 50, 80), Snap(5, 50, 80), None, None),
    # a pipeline from before the counters existed
    (Bare(), Bare(), None, None),
])
def test_counter_readers(s0, s1, steps_per_query, lane_use):
    got = _reader("knn_steps_per_query")(_run(s0, s1))
    use = _reader("knn_lane_use")(_run(s0, s1))
    if steps_per_query is None:
        assert got is None and use is None
    else:
        assert got == pytest.approx(steps_per_query)
        assert use == pytest.approx(lane_use)


def test_traced_run_reads_the_loop_counters(monkeypatch):
    """The cell at a CPU size, served by the engine's loop route: the
    counter readers find the window's kNN rows; the device-trace readers
    find no device plane on the CPU and report nothing."""
    import jax

    from bench import run, system
    from repro.core.engine import EngineConfig, QueryEngine
    monkeypatch.setattr(system, "ServingPipeline", functools.partial(
        system.ServingPipeline,
        engine=QueryEngine(EngineConfig(force="loop"))))
    out = run.run_cell(tiny(spec.load_cell(CELL)), jax.devices()[:1], 7,
                       1.0, True, time.perf_counter())
    res = out.result
    assert res["correct"] is True, out.check.values
    assert set(res["metrics"]) == {"knn_steps_per_query", "knn_lane_use"}
    steps = res["metrics"]["knn_steps_per_query"]["value"]
    assert steps >= 1 and res["metrics"]["knn_steps_per_query"]["unit"] == \
        "nodes/query"
    assert 0 < res["metrics"]["knn_lane_use"]["value"] <= 100

"""The reader of the kNN seed's engagement, ``knn_seed_share``: the share
of the loop's real rows whose pruning bound was seeded, from the
pipeline's counters, and a traced run of the kNN cell at a CPU size that
reads it with the cell's listed metrics."""
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
    knn_seeded_rows: int = 0


@dataclasses.dataclass
class Unseeded:
    """A snapshot of a pipeline whose loop counts rows but no seed."""
    knn_rows: int = 0


def _read(s0, s1):
    return spec.load_reader(ROOT, "knn_seed_share")(
        Run(None, None, None, s0, s1, None))


@pytest.mark.parametrize("s0,s1,share", [
    (Snap(), Snap(1024, 1024), 100.0),
    (Snap(2048, 1000), Snap(6144, 4072), 3072 / 4096 * 100),
    (Snap(100, 40), Snap(300, 40), 0.0),
    # no kNN row served by the loop in the window
    (Snap(5, 5), Snap(5, 5), None),
    # a pipeline from before the seed's counter existed
    (Unseeded(0), Unseeded(1024), None),
])
def test_seed_share_reader(s0, s1, share):
    got = _read(s0, s1)
    assert got == (None if share is None else pytest.approx(share))


def test_traced_run_reads_the_seed_share(monkeypatch):
    """The kNN cell at a CPU size through the engine's loop route, with
    the share read beside the cell's listed metrics: every row of the
    uniform cloud is seeded."""
    import jax

    from bench import run, system
    from repro.core.engine import EngineConfig, QueryEngine
    monkeypatch.setattr(system, "ServingPipeline", functools.partial(
        system.ServingPipeline,
        engine=QueryEngine(EngineConfig(force="loop"))))
    cell = tiny(spec.load_cell(CELL))
    share = spec.Metric("knn_seed_share", "%", "higher", "program_counter",
                        "qps", (CELL,), spec.load_reader(ROOT,
                                                         "knn_seed_share"))
    cell = dataclasses.replace(cell, per_layer=cell.per_layer + (share,))
    out = run.run_cell(cell, jax.devices()[:1], 2147483901, 1.0, True,
                       time.perf_counter())
    res = out.result
    assert res["correct"] is True, out.check.values
    assert res["metrics"]["knn_seed_share"] == {"value": 100.0, "unit": "%"}
    assert res["metrics"]["knn_steps_per_query"]["value"] >= 1

"""CPU tests of the benchmark harness (``bench/``). They put the library
on the path themselves, as ``bench.run`` does."""
import dataclasses
import functools
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
SRC = os.path.join(ROOT, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)


def cell_of(name: str):
    """The cell `name` (``<config>.<traffic>``), listed or not."""
    from bench import spec
    return spec.find_cell(name)


def tiny(cell, points: int = 512, rate: float = 40.0):
    """`cell` at a size the CPU serves in seconds: few points, buckets of
    up to 32 rows, and for an open mix updates every half second."""
    cfg = dict(cell.config, points=points, max_bucket=32)
    mix = dict(cell.traffic)
    if mix["loop"] == "closed":
        mix["rows"] = 32
    else:
        mix.update(rate_rps=rate, rows={"loguniform": [1, 16]})
        if "updates" in mix:
            mix["updates"] = dict(mix["updates"], first_s=0.3, every_s=0.5)
    return dataclasses.replace(cell, config=cfg, traffic=mix)


@pytest.fixture
def tiny_cell():
    return tiny


@pytest.fixture(scope="module")
def shared_engine():
    """Every ``System`` the module makes serves through one ``QueryEngine``,
    so that a test reuses the executables an earlier one compiled."""
    from bench import system
    from repro.core.engine import QueryEngine
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(system, "ServingPipeline",
                   functools.partial(system.ServingPipeline,
                                     engine=QueryEngine()))
        yield

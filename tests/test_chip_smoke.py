"""CPU rehearsal of ``chip_smoke.py``: its entry point refuses a non-TPU
backend, and its phases — the same code the chip runs — pass at a tiny
size here, with the engine's brute-force crossover scaled down so the
routes match what the chip takes (loop, and brute force for the small
batches)."""
import os
import sys

import numpy as np
import pytest

from repro.core import engine as E

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402


def test_refuses_a_cpu_backend_and_prints_no_result(capsys):
    assert chip_smoke.main([]) != 0
    assert chip_smoke.main(["--chips", "4"]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_refuses_repro_sanitize(monkeypatch, capsys):
    monkeypatch.setenv("REPRO_SANITIZE", "1")
    assert chip_smoke.main([]) != 0
    assert "REPRO_SANITIZE" in capsys.readouterr().err


def _tpu_routes_engine(monkeypatch, bf_max_work: int):
    """The chip's rule with the brute-force crossover scaled down to the
    rehearsal's sizes."""
    monkeypatch.setattr(E, "BF_MAX_WORK", bf_max_work)
    return E.QueryEngine()


def test_single_chip_phases_pass_at_tiny_size(monkeypatch):
    routes = chip_smoke.smoke_single(
        n_large=2048, n_small=512, q=64, small_q=8, seed=3,
        engine=_tpu_routes_engine(monkeypatch, 512 * 8))
    assert routes["large Q=64"] == {"knn": "loop", "within": "loop",
                                    "ray": "loop"}
    assert routes["small Q=8"] == {"knn": "bruteforce",
                                   "within": "bruteforce", "ray": "loop"}
    assert set(routes) == {"large Q=64", "large Q=64 refit", "small Q=64",
                           "small Q=8", "small Q=64 refit",
                           "small Q=8 refit"}


def test_sharded_phase_passes_on_four_host_devices(subproc):
    out = subproc(f"""
import sys
sys.path.insert(0, {REPO!r})
import jax
import chip_smoke
from repro.compat import make_mesh
mesh = make_mesh((4,), ("data",))
routes = chip_smoke.smoke_sharded(mesh, n_per_shard=512, q=64, seed=5)
assert set(routes) == {{"sharded Q=64", "sharded Q=64 refit"}}
assert all(r == "sharded" for by in routes.values() for r in by.values())
print("SHARDED OK")
""", devices=4)
    assert "SHARDED OK" in out


def test_reference_grid_matches_a_full_scan():
    """The reference reads only nearby grid cells; on points that spill
    out of the unit cube it must still find exactly what scanning every
    point finds (knn tie band, within ball) and every ray hit."""
    rng = np.random.default_rng(1)
    pts = chip_smoke.jittered(rng, chip_smoke.uniform_points(rng, 20000))
    ref = chip_smoke.Reference(pts)
    q = rng.random((24, 3), dtype=np.float32)
    q[:4] = [[0, 0, 0], [1, 1, 1], [0.5, 0, 1], [-0.02, 0.5, 1.02]]
    reach = chip_smoke.radius_for(len(pts)) * (1 + 1e-3)
    for c in q:
        d2 = np.sum((pts - c) ** 2, axis=1)
        band = np.partition(d2, 7)[7] * (1 + 1e-4) + 1e-12
        assert set(ref._knn_candidates(c, 8)) == set(np.nonzero(d2 <= band)[0])
        box = ref._in_box(c - reach, c + reach)
        assert set(box[ref._d2_f32(c, box) <= reach ** 2]) == \
            set(np.nonzero(d2 <= reach ** 2)[0])
    # rays aimed at points: the check finds a hit for every aimed ray
    o, d = chip_smoke.make_rays(rng, pts, 32)
    idx = np.full((32, 1), -1)
    ts = np.full((32, 1), np.inf, np.float32)
    with pytest.raises(AssertionError):       # all-miss answers are wrong
        ref.check_ray(o, d, ts, idx, range(16))


def test_reference_catches_a_wrong_answer():
    """The numpy checks are the run's verdict: a perturbed answer fails."""
    rng = np.random.default_rng(0)
    pts = chip_smoke.uniform_points(rng, 4096)
    ref = chip_smoke.Reference(pts)
    q = pts[:4] + np.float32(1e-3)
    d = np.linalg.norm(pts[None, :, :] - q[:, None, :], axis=-1)
    idx = np.argsort(d, axis=1)[:, :8]
    dist = np.take_along_axis(d, idx, 1).astype(np.float32)
    ref.check_knn(q, dist, idx, range(4))
    bad = idx.copy()
    bad[0, 7] = np.argsort(d[0])[8]               # ninth neighbour instead
    with pytest.raises(AssertionError):
        ref.check_knn(q, dist, bad, range(4))

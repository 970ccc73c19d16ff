"""ISSUE 7 conformance: (a) the fused Pallas build pipeline produces
trees BIT-IDENTICAL to the reference build — topology AND bounds, every
LBVH field — and (b) a RouteTable can only ever change WHICH execution
path serves a query, never its result (adversarial tables included)."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import callbacks as CB
from repro.core import geometry as G
from repro.core import predicates as P
from repro.core.bvh import BVH
from repro.core.index import ExecutionPolicy
from repro.core.lbvh import LBVH, _resolve_build_engine, build, refit
from repro.core.route_table import RouteTable


def _pts(n, dim=3, seed=0):
    r = np.random.default_rng(seed)
    return jnp.asarray(r.uniform(0, 1, (n, dim)).astype(np.float32))


def _assert_trees_identical(a, b):
    for f in dataclasses.fields(LBVH):
        x = np.asarray(getattr(a, f.name))
        y = np.asarray(getattr(b, f.name))
        assert np.array_equal(x, y), f"LBVH field {f.name} diverged"


# ---------------------------------------------------------------------------
# fused build == reference build, node for node
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,dim,bits", [(2, 3, 64), (33, 2, 64),
                                        (257, 3, 32), (1000, 5, 64),
                                        (4096, 3, 64)])
def test_pallas_build_bit_identical_to_ref(n, dim, bits):
    pts = _pts(n, dim, seed=n)
    boxes = G.Boxes(pts, pts + 0.01)
    _assert_trees_identical(build(boxes, bits=bits, engine="ref"),
                            build(boxes, bits=bits, engine="pallas"))


def test_pallas_build_identical_with_duplicate_codes():
    """Duplicate points -> equal Morton codes -> the index tie-break path
    of the Karras delta must agree between engines."""
    base = _pts(64, 3, seed=9)
    pts = jnp.concatenate([base, base, base[:17]], axis=0)
    boxes = G.Boxes(pts, pts)
    _assert_trees_identical(build(boxes, engine="ref"),
                            build(boxes, engine="pallas"))


def test_pallas_build_identical_on_clustered_data():
    from repro.data import point_cloud
    pts = jnp.asarray(point_cloud("clusters", 2048, seed=3))
    boxes = G.Boxes(pts, pts)
    for bits in (32, 64):
        _assert_trees_identical(build(boxes, bits=bits, engine="ref"),
                                build(boxes, bits=bits, engine="pallas"))


def test_refit_agrees_across_build_engines():
    pts = _pts(512, 3, seed=4)
    boxes = G.Boxes(pts, pts)
    moved = G.Boxes(pts * 0.5 + 0.1, pts * 0.5 + 0.2)
    _assert_trees_identical(refit(build(boxes, engine="ref"), moved),
                            refit(build(boxes, engine="pallas"), moved))


def test_build_engine_env_force_wins(monkeypatch):
    """REPRO_ENGINE_FORCE beats the explicit engine argument (the
    documented debugging override; DESIGN.md §8)."""
    monkeypatch.setenv("REPRO_ENGINE_FORCE", "loop")
    assert _resolve_build_engine("pallas") == "ref"
    monkeypatch.setenv("REPRO_ENGINE_FORCE", "pallas")
    assert _resolve_build_engine("ref") == "pallas"
    monkeypatch.delenv("REPRO_ENGINE_FORCE")
    assert _resolve_build_engine("ref") == "ref"


def test_bvh_build_engine_kwarg():
    vals = G.Points(_pts(256, 3, seed=11))
    a = BVH(vals, build_engine="ref")
    b = BVH(vals, build_engine="pallas")
    assert a.policy.build_engine == "ref"
    _assert_trees_identical(a.tree, b.tree)


def test_karras_kernel_refuses_more_leaves_than_declared():
    """The kernel stages its key tables whole in VMEM: past its declared
    leaf limit it raises instead of building a program that cannot fit."""
    from repro.kernels import lbvh_build as KB
    n = KB.PALLAS_MAX_LEAVES + 1
    z = jnp.zeros((n,), jnp.uint32)
    with pytest.raises(ValueError, match="at most"):
        KB.karras_ranges_pallas(z, z, z, n, 21)


def test_builtin_route_table_keeps_tpu_off_the_traversal_kernels():
    """Lookup step 5: the defaults off a TPU; on one, every op's
    ``pallas_max_nodes`` is 0 and the table says why."""
    from repro.core.route_table import builtin_route_table
    assert builtin_route_table().source == "defaults"     # CPU here
    tpu = RouteTable.tpu_default()
    assert tpu.source == "tpu-rule" and "do not compile" in tpu.reason
    for op in ("spatial", "knn", "callback"):
        assert tpu.rule(op).pallas_max_nodes == 0
        assert tpu.rule(op).bf_max_work == \
            RouteTable.default().rule(op).bf_max_work


# ---------------------------------------------------------------------------
# adversarial route tables: latency-only, never results
# ---------------------------------------------------------------------------

_ADVERSARIAL = [
    RouteTable.single(),                                 # built-in defaults
    RouteTable.single(bf_max_work=1 << 40),              # everything -> MXU
    RouteTable.single(bf_max_work=0, pallas_min_queries=1,   # everything ->
                      pallas_min_leaves=1,                   # fused kernel,
                      pallas_max_nodes=1 << 30, block_q=8),  # absurd block
    RouteTable.single(bf_max_work=0,
                      pallas_min_queries=1 << 30),       # everything -> loop
    RouteTable.single(pallas_max_nodes=1),               # kernel "never fits"
    RouteTable.single(bf_max_work=0, pallas_max_capacity=0),
    RouteTable.tpu_default(),                            # TPU built-in rule
]


def test_adversarial_route_tables_change_latency_not_results():
    vals = G.Points(_pts(400, 3, seed=7))
    qp = _pts(32, 3, seed=8)
    preds = P.intersects(G.Spheres(qp, jnp.full((32,), 0.25, jnp.float32)))
    knn = P.nearest(G.Points(qp), k=4)
    cb, s0 = CB.counting()

    # pure while-loop reference
    ref = BVH(vals, policy=ExecutionPolicy(route_table=RouteTable.single(
        bf_max_work=0, pallas_min_queries=1 << 30)))
    want_cnt = np.asarray(ref.count(preds))
    rref = ref.query(preds)
    off = np.asarray(rref.offsets)
    iref = np.asarray(rref.indices)
    want_d = np.asarray(ref.query(knn).distances)
    want_cb = np.asarray(ref.query(preds, callback=(cb, s0)))

    for tbl in _ADVERSARIAL:
        bvh = BVH(vals, policy=ExecutionPolicy(route_table=tbl))
        assert np.array_equal(np.asarray(bvh.count(preds)), want_cnt)
        res = bvh.query(preds)
        assert np.array_equal(np.asarray(res.offsets), off)
        idx = np.asarray(res.indices)
        for i in range(32):          # per-query match SETS (order may vary)
            assert set(idx[off[i]:off[i + 1]].tolist()) == \
                set(iref[off[i]:off[i + 1]].tolist())
        assert np.allclose(np.asarray(bvh.query(knn).distances), want_d,
                           atol=1e-5)
        assert np.array_equal(
            np.asarray(bvh.query(preds, callback=(cb, s0))), want_cb)

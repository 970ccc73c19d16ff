"""Build conformance: the fused Pallas build pipeline produces trees
BIT-IDENTICAL to the reference build — topology AND bounds, every LBVH
field."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import geometry as G
from repro.core.bvh import BVH
from repro.core.lbvh import LBVH, _resolve_build_engine, build, refit


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


def test_build_engine_resolution():
    """An explicit engine is kept; "auto" is the fused build; anything
    else is refused."""
    assert _resolve_build_engine("ref") == "ref"
    assert _resolve_build_engine("pallas") == "pallas"
    assert _resolve_build_engine("auto") == "pallas"
    with pytest.raises(ValueError, match="engine="):
        _resolve_build_engine("loop")


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

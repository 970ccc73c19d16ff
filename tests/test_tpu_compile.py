"""TPU compile rehearsal: the programs the TPU route can select, compiled
by the TPU compiler for a described v5e chip (no chip attached), at the
largest sizes the route allows.

Every test here runs in this one file's worker: the topology is described
inside the module-scoped ``topo`` fixture, never at import, so only the
worker given this file loads the TPU compiler. The Karras kernel, kept
OFF the TPU build (the jit twin runs there), carries a strict xfail with
the compiler's message: the PR that makes it compile flips it.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import callbacks as CB
from repro.core import geometry as G
from repro.core import lbvh
from repro.core import predicates as P
from repro.core import traversal as T
from repro.core import engine as E
from repro.core.brute_force import BruteForce
from repro.kernels import lbvh_build as KB
from repro.kernels.bruteforce_knn import bruteforce_knn_pallas

N_CHIP = 1 << 22          # the single-chip bring-up size (chip_smoke.py)
Q_BUCKET = 1024           # its largest serving bucket
HBM_BUDGET = 12e9         # a program's temporaries must leave room for the
                          # index versions on a 16 GB v5e
f32, i32, u32 = jnp.float32, jnp.int32, jnp.uint32


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache: keep it out while these tests run
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def spec(one_chip):
    """shape, dtype -> an argument placed on the described chip."""
    return lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=one_chip)


def _tree(spec, n, dim=3):
    m = 2 * n - 1
    lo, hi = spec((m, dim), f32), spec((m, dim), f32)
    left, rope, rlast = spec((n - 1,), i32), spec((m,), i32), spec((m,), i32)
    perm = spec((n,), i32)
    table = jax.eval_shape(lbvh._pack_nodes, lo, hi, rope, rlast, left, perm)
    return lbvh.LBVH(lo, hi, left, spec((n - 1,), i32), rope, rlast, perm,
                     spec((n - 1,), i32), spec(table.shape, table.dtype))


def _temp_bytes(compiled):
    return compiled.memory_analysis().temp_size_in_bytes


# ---------------------------------------------------------------------------
# on the TPU route
# ---------------------------------------------------------------------------

def test_build_at_chip_size_fits_hbm(spec):
    """The build every create_index runs (fused delta-RMQ jit twin), at
    2^22 points: the compiler put 4.5 GB of temporaries on v5e."""
    fn = jax.jit(lambda lo, hi: lbvh._build_impl(
        G.Boxes(lo, hi), bits=64, refit="rmq", engine="pallas"))
    c = fn.lower(spec((N_CHIP, 3), f32), spec((N_CHIP, 3), f32)).compile()
    assert _temp_bytes(c) < HBM_BUDGET


def test_refit_at_chip_size_fits_hbm(spec):
    fn = jax.jit(lambda t, v: lbvh.refit(t, G.Boxes(v, v)))
    c = fn.lower(_tree(spec, N_CHIP), spec((N_CHIP, 3), f32)).compile()
    assert _temp_bytes(c) < HBM_BUDGET


def _loop_knn(t, v, a, b):
    return T.traverse_knn(t, G.Points(v), P.nearest(G.Points(a), k=8), 8)


def _loop_knn_steps(t, v, a, b):
    """The engine's kNN loop route: the same walk, counting its steps."""
    return T.traverse_knn(t, G.Points(v), P.nearest(G.Points(a), k=8), 8,
                          count_steps=True)


def _loop_within(t, v, a, b):
    cb, s0 = CB.collect_hits(64)
    s0 = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (a.shape[0],) + jnp.shape(x)), s0)
    return T.traverse(t, G.Points(v),
                      P.intersects(G.Spheres(a, b[:, 0])), cb, s0)


def _loop_ray(t, v, a, b):
    return T.traverse_knn(t, G.Points(v), P.RayNearest(G.Rays(a, b), 1), 1)


LOOPS = pytest.mark.parametrize(
    "body", [_loop_knn, _loop_knn_steps, _loop_within, _loop_ray],
    ids=["knn", "knn_steps", "within", "ray"])


def _compile_loop(spec, body):
    return jax.jit(body).lower(
        _tree(spec, N_CHIP), spec((N_CHIP, 3), f32),
        spec((Q_BUCKET, 3), f32), spec((Q_BUCKET, 3), f32)).compile()


@LOOPS
def test_loop_traversal_at_chip_size_fits_hbm(spec, body):
    """The while-loop route every served kind takes on TPU. Its
    temporaries (~2.15 GB here) are the (N, 3) values relaid out with the
    3-wide minor dimension padded to 128 lanes; the node arrays, relaid
    out the same way, added ~8.6 GB until the loop read the packed node
    table."""
    assert _temp_bytes(_compile_loop(spec, body)) < HBM_BUDGET


@LOOPS
def test_loop_traversal_reads_the_node_table_in_place(spec, body):
    """Each step gathers a lane-dense row of the node table in the layout
    it is stored in: no (2N-1, dim) node array is copied before the loop,
    and what temporaries remain are the values' relayout."""
    c = _compile_loop(spec, body)
    copied = re.findall(r"= \w+\[(\d+)(?:,\d+)*\]\S* copy\(", c.as_text())
    assert str(2 * N_CHIP - 1) not in copied
    assert _temp_bytes(c) < 3e9


@pytest.mark.parametrize("op", ["knn", "fill"])
def test_bruteforce_route_compiles(spec, op):
    """The brute-force route at its crossover: N*Q = BF_MAX_WORK."""
    n = 1 << 16
    q = E.BF_MAX_WORK // n

    def fn(v, a):
        bf = BruteForce(G.Points(v))
        if op == "knn":
            return bf._knn_impl(P.nearest(G.Points(a), k=8), bf.policy)
        preds = P.intersects(G.Spheres(a, jnp.full((q,), 0.04, f32)))
        return bf._fill_impl(preds, 64, bf.policy)

    jax.jit(fn).lower(spec((n, 3), f32), spec((q, 3), f32)).compile()


def test_bruteforce_knn_kernel_compiles(spec):
    """The ``ops.bruteforce_knn`` kernel (not on the engine's route) at a
    serving bucket, lane-padded as ``ops`` pads it: a real Mosaic kernel
    in the compiled program."""
    n = 1 << 16
    fn = jax.jit(lambda a, v: bruteforce_knn_pallas(
        a, v, 8, n_actual=n, bq=64, bn=512, interpret=False))
    c = fn.lower(spec((64, 128), f32), spec((n, 128), f32)).compile()
    assert "tpu_custom_call" in c.as_text()


# ---------------------------------------------------------------------------
# off the TPU route by an explicit rule — a strict xfail
# ---------------------------------------------------------------------------

@pytest.mark.xfail(strict=True, raises=NotImplementedError,
                   reason=KB.PALLAS_TPU_REASON)
def test_karras_kernel_compiles_at_its_leaf_limit(spec):
    n = KB.PALLAS_MAX_LEAVES
    fn = jax.jit(lambda h, lo, ix: KB.karras_ranges_pallas(
        h, lo, ix, n, 20, interpret=False))
    fn.lower(spec((n,), u32), spec((n,), u32), spec((n,), u32)).compile()

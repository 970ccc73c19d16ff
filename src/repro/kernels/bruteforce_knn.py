"""Pallas TPU kernel: brute-force k-nearest-neighbors.

The TPU-native form of ArborX's brute-force index (§1, DESIGN.md §2): the
pairwise squared-distance matrix

    d2 = ||q||^2 - 2 q @ p^T + ||p||^2

is evaluated panel-by-panel on the MXU, with a streaming top-k merge so
the (Q, N) matrix never leaves VMEM.

Tiling: grid = (Q/bq, N/bn); the N dimension is the minor (sequential)
grid axis, so the (bq, k) running-best scratch lives in VMEM across the
whole sweep of one query block. Coordinates are zero-padded to lane width
(128) by the ops.py wrapper — zero padding leaves euclidean distances
unchanged and keeps the MXU contraction dimension aligned.

The k-smallest selection is k rounds of (min, mask) over the concatenated
candidate row — branch-free, vector-wide, and the output arrives sorted
ascending (ties broken toward the lower index).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._compat import compiler_params

_BIG = float("inf")


def _select_k(cand_d, cand_i, k: int):
    """k rounds of extract-min over rows of (bq, C). Returns (bq, k) x2,
    sorted ascending, index tie-break."""
    bq, c = cand_d.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (bq, c), 1)
    out_d = []
    out_i = []
    for _ in range(k):
        m = jnp.min(cand_d, axis=1, keepdims=True)            # (bq, 1)
        is_min = cand_d == m
        # first column achieving the min
        first = jnp.min(jnp.where(is_min, col, c), axis=1, keepdims=True)
        sel = col == first
        out_d.append(m[:, 0])
        out_i.append(jnp.sum(jnp.where(sel, cand_i, 0), axis=1))
        cand_d = jnp.where(sel, _BIG, cand_d)
    return jnp.stack(out_d, 1), jnp.stack(out_i, 1)


def _knn_kernel(q_ref, p_ref, dout_ref, iout_ref, run_d, run_i,
                *, k: int, bn: int, n_actual: int, num_panels: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        run_d[...] = jnp.full_like(run_d, jnp.inf)
        run_i[...] = jnp.full_like(run_i, -1)

    q = q_ref[...].astype(jnp.float32)                        # (bq, D)
    p = p_ref[...].astype(jnp.float32)                        # (bn, D)
    q2 = jnp.sum(q * q, axis=1, keepdims=True)                # (bq, 1)
    p2 = jnp.sum(p * p, axis=1)[None, :]                      # (1, bn)
    # HIGHEST: a TPU's default float32 matmul is one bfloat16 pass
    qp = jax.lax.dot_general(q, p, (((1,), (1,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)  # MXU
    d2 = jnp.maximum(q2 - 2.0 * qp + p2, 0.0)                 # (bq, bn)

    base = j * bn
    pidx = base + jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1)
    valid = pidx < n_actual
    d2 = jnp.where(valid, d2, jnp.inf)

    cand_d = jnp.concatenate([run_d[...], d2], axis=1)
    cand_i = jnp.concatenate([run_i[...], pidx], axis=1)
    new_d, new_i = _select_k(cand_d, cand_i, k)
    run_d[...] = new_d
    run_i[...] = new_i

    @pl.when(j == num_panels - 1)
    def _finalize():
        dout_ref[...] = jnp.sqrt(run_d[...])
        iout_ref[...] = run_i[...]


def bruteforce_knn_pallas(queries, points, k: int, *, n_actual: int | None = None,
                          bq: int = 256, bn: int = 512, interpret: bool = False):
    """queries (Q, D), points (N, D) — D already lane-padded. Returns
    (dists, idx): (Q, k) float32/int32, ascending."""
    q_, d = queries.shape
    n_, _ = points.shape
    assert q_ % bq == 0 and n_ % bn == 0, "ops.py pads to block multiples"
    num_panels = n_ // bn
    grid = (q_ // bq, num_panels)
    if n_actual is None:
        n_actual = n_

    kernel = functools.partial(_knn_kernel, k=k, bn=bn, n_actual=n_actual,
                               num_panels=num_panels)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q_, k), jnp.float32),
            jax.ShapeDtypeStruct((q_, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, k), jnp.float32),
            pltpu.VMEM((bq, k), jnp.int32),
        ],
        compiler_params=compiler_params(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(queries, points)


# ---------------------------------------------------------------------------
# reprolint sanitizer spec (analysis/pallas_trace.py)
# ---------------------------------------------------------------------------

#: largest k the streaming top-k scratch is declared for: the running-best
#: scratch is (bq, k) x2, resident all sweep long, so k bounds its VMEM
REPROLINT_MAX_K = 256


def REPROLINT_SPECS():
    def knn_launch():
        bq, bn, d = 256, 512, 128
        bruteforce_knn_pallas(
            jnp.zeros((bq, d), jnp.float32), jnp.zeros((4 * bn, d),
                                                       jnp.float32),
            REPROLINT_MAX_K, bq=bq, bn=bn, interpret=True)

    return [{"name": "bruteforce-knn@max-k", "call": knn_launch}]

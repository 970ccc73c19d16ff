"""Pallas TPU kernel: fused rope-based stackless BVH traversal (§2.6).

This is the TPU spelling of ArborX's per-thread stackless walk (Prokopenko
& Lebrun-Grandié 2024): one grid cell owns a *block of queries*, the whole
flat tree (``node_lo/hi``, ``rope``, ``left_child``, ``range_last``,
``leaf_perm``) is staged through VMEM once per block, and the only per-query
traversal state is a single int32 node cursor per lane. Every loop step the
block gathers its cursors' node boxes, runs the overlap / distance test
vector-wide, bumps matched counts (or merges kNN candidates), and advances
each lane to either ``left_child`` (descend) or ``rope`` (escape) — no
stacks, no divergence beyond the shared loop trip count, which is the
longest rope walk in the block.

Two kernels:

  * ``_spatial_kernel``: intersects-style queries in the unified
    (q_lo, q_hi, r²) representation — a point is a degenerate box with
    r = 0, a sphere a degenerate box with r > 0 — so point/box/sphere
    predicates share one code path whose leaf test is *bit-identical* to
    ``geometry.intersects_box_{point,box,sphere}`` (the BruteForce oracle).
    Emits per-query match counts plus the first ``capacity`` matched
    original indices in traversal order (the CSR fill pass). The
    pair-traversal position filter (``range_last > min_pos``) is included,
    so a strict upper-triangle self-join runs in-kernel too.
  * ``_knn_kernel``: k-nearest with squared-distance pruning against the
    running k-th best (tau), and a branch-free sorted insertion into the
    per-lane (k,) candidate lists — the TPU form of the best-first
    traversal, in rope order with tau-tightening.

On CPU backends the kernels run in interpret mode (identical semantics,
what the oracle tests assert against). They do not compile for TPU yet:
Mosaic rejects the per-lane row gathers (``_take``) with "Shape mismatch
in input, indices and output", so the built-in TPU route table
(``RouteTable.tpu_default``) keeps every op off them. Were they to
compile, the tree tables would still have to fit VMEM (~16 MB): ~2¹⁷
nodes (~6·10⁴ leaves) at dim ≤ 8, the default ``pallas_max_nodes``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import sanitize
from ._compat import compiler_params
from .ops import _pad_cols, _pad_rows, _round_up

__all__ = ["bvh_traverse_spatial", "bvh_traverse_knn"]


def _take(arr, idx):
    """Clipped gather — rows of `arr` at int32 `idx` (OOB clamps)."""
    return jnp.take(arr, idx, axis=0, mode="clip")


# ---------------------------------------------------------------------------
# spatial: count + collect-first-capacity
# ---------------------------------------------------------------------------

def _spatial_kernel(qlo_ref, qhi_ref, r_ref, minpos_ref, node_lo_ref,
                    node_hi_ref, rope_ref, left_ref, rlast_ref, perm_ref,
                    count_ref, idx_ref, *, n: int, cap: int, fine_sqrt: bool):
    qlo = qlo_ref[...].astype(jnp.float32)         # (bq, dim_p)
    qhi = qhi_ref[...].astype(jnp.float32)
    r = r_ref[...].astype(jnp.float32)             # (bq,)
    r2 = r * r                                     # same op as geometry.py
    min_pos = minpos_ref[...]                      # (bq,)
    node_lo = node_lo_ref[...].astype(jnp.float32)  # (2n-1, dim_p)
    node_hi = node_hi_ref[...].astype(jnp.float32)
    rope = rope_ref[...]                           # (2n-1,)
    left = left_ref[...]                           # (n-1,)
    rlast = rlast_ref[...]                         # (2n-1,)
    perm = perm_ref[...]                           # (n,)

    bq = qlo.shape[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (bq, cap), 1)

    def cond(carry):
        return jnp.any(carry[0] != -1)

    def body(carry):
        node, cnt, buf = carry
        active = node != -1
        nd = jnp.where(active, node, 0)

        lo = _take(node_lo, nd)                    # (bq, dim_p)
        hi = _take(node_hi, nd)
        # distance² from the query box to the node box; ≤ r² is exactly
        # intersects_box_point / _box / _sphere for the three query kinds
        g = jnp.maximum(jnp.maximum(qlo - hi, lo - qhi), 0.0)
        d2 = jnp.sum(g * g, axis=1)
        pos_ok = _take(rlast, nd) > min_pos        # pair-traversal filter
        overlap = (d2 <= r2) & pos_ok & active

        is_leaf = nd >= n - 1
        leaf_pos = jnp.clip(nd - (n - 1), 0, n - 1)
        orig = _take(perm, leaf_pos)
        # leaf box == value box for box-testable values, so `overlap` at a
        # leaf IS the fine test — except Points values under sphere queries,
        # whose fine test is the sqrt form (distance <= r); fine_sqrt makes
        # the leaf decision bit-identical to traversal._leaf_test there
        hit = is_leaf & overlap
        if fine_sqrt:
            hit = hit & (jnp.sqrt(d2) <= r)
        put = hit[:, None] & (col == cnt[:, None])  # cnt >= cap: no column
        buf = jnp.where(put, orig[:, None], buf)
        cnt = cnt + hit.astype(jnp.int32)

        descend = overlap & ~is_leaf
        nxt = jnp.where(descend, _take(left, jnp.minimum(nd, n - 2)),
                        _take(rope, nd))
        return jnp.where(active, nxt, -1), cnt, buf

    node0 = jnp.zeros((bq,), jnp.int32)            # every lane starts at root
    cnt0 = jnp.zeros((bq,), jnp.int32)
    buf0 = jnp.full((bq, cap), -1, jnp.int32)
    _, cnt, buf = jax.lax.while_loop(cond, body, (node0, cnt0, buf0))
    count_ref[...] = cnt
    idx_ref[...] = buf


def bvh_traverse_spatial(node_lo, node_hi, rope, left_child, range_last,
                         leaf_perm, q_lo, q_hi, radius, *, capacity: int = 1,
                         fine_sqrt: bool = False, min_pos=None, bq: int = 256,
                         interpret: bool | None = None):
    """Fused stackless traversal for a batch of spatial predicates.

    Tree arrays are the LBVH fields; queries are (Q, dim) boxes plus a (Q,)
    radius (0 for point/box predicates). `fine_sqrt` selects the sqrt-form
    leaf test (``distance <= r``) used for Points values, vs the squared
    box test used for Boxes values — matching ``predicates.leaf_match_test``
    bit-for-bit either way. Returns (counts (Q,) int32, idx_buf
    (Q, capacity) int32): full match counts and the first `capacity`
    matched original indices in traversal order (-1 padding) — the exact
    contract of ``callbacks.collect_hits``.
    """
    counts, buf = _bvh_traverse_spatial_jit(
        node_lo, node_hi, rope, left_child, range_last, leaf_perm,
        q_lo, q_hi, radius, capacity=capacity, fine_sqrt=fine_sqrt,
        min_pos=min_pos, bq=bq, interpret=interpret)
    sanitize.check_spatial(counts, buf, n=leaf_perm.shape[0],
                           kernel="bvh_traverse_spatial")
    return counts, buf


@functools.partial(jax.jit, static_argnames=("capacity", "fine_sqrt", "bq",
                                             "interpret"))
def _bvh_traverse_spatial_jit(node_lo, node_hi, rope, left_child, range_last,
                              leaf_perm, q_lo, q_hi, radius, *,
                              capacity: int = 1, fine_sqrt: bool = False,
                              min_pos=None, bq: int = 256,
                              interpret: bool | None = None):
    if interpret is None:
        interpret = sanitize.interpret_default()
    q, dim = q_lo.shape
    n = leaf_perm.shape[0]
    if q == 0:
        return (jnp.zeros((0,), jnp.int32),
                jnp.zeros((0, capacity), jnp.int32))
    dim_p = _round_up(dim, 8)
    bq_eff = min(bq, _round_up(q, 8))
    qp = _round_up(q, bq_eff)

    # padded queries hit nothing: +inf box corners give d² = +inf
    qlo_p = _pad_cols(_pad_rows(q_lo.astype(jnp.float32), qp, jnp.inf), dim_p)
    qhi_p = _pad_cols(_pad_rows(q_hi.astype(jnp.float32), qp, jnp.inf), dim_p)
    r_p = _pad_rows(radius.astype(jnp.float32), qp, 0.0)
    mp = jnp.full((q,), -1, jnp.int32) if min_pos is None else min_pos
    mp_p = _pad_rows(mp.astype(jnp.int32), qp, -1)
    nlo = _pad_cols(node_lo.astype(jnp.float32), dim_p)
    nhi = _pad_cols(node_hi.astype(jnp.float32), dim_p)

    m = nlo.shape[0]                                # 2n - 1
    kernel = functools.partial(_spatial_kernel, n=n, cap=capacity,
                               fine_sqrt=fine_sqrt)
    counts, buf = pl.pallas_call(
        kernel,
        grid=(qp // bq_eff,),
        in_specs=[
            pl.BlockSpec((bq_eff, dim_p), lambda i: (i, 0)),
            pl.BlockSpec((bq_eff, dim_p), lambda i: (i, 0)),
            pl.BlockSpec((bq_eff,), lambda i: (i,)),
            pl.BlockSpec((bq_eff,), lambda i: (i,)),
            pl.BlockSpec((m, dim_p), lambda i: (0, 0)),
            pl.BlockSpec((m, dim_p), lambda i: (0, 0)),
            pl.BlockSpec((m,), lambda i: (0,)),
            pl.BlockSpec((n - 1,), lambda i: (0,)),
            pl.BlockSpec((m,), lambda i: (0,)),
            pl.BlockSpec((n,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((bq_eff,), lambda i: (i,)),
            pl.BlockSpec((bq_eff, capacity), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((qp,), jnp.int32),
            jax.ShapeDtypeStruct((qp, capacity), jnp.int32),
        ],
        compiler_params=compiler_params(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(qlo_p, qhi_p, r_p, mp_p, nlo, nhi, rope, left_child, range_last,
      leaf_perm)
    return counts[:q], buf[:q]


# ---------------------------------------------------------------------------
# k-nearest
# ---------------------------------------------------------------------------

def _knn_kernel(q_ref, node_lo_ref, node_hi_ref, rope_ref, left_ref,
                perm_ref, dist_ref, idx_ref, *, n: int, k: int):
    qc = q_ref[...].astype(jnp.float32)            # (bq, dim_p)
    node_lo = node_lo_ref[...].astype(jnp.float32)
    node_hi = node_hi_ref[...].astype(jnp.float32)
    rope = rope_ref[...]
    left = left_ref[...]
    perm = perm_ref[...]

    bq = qc.shape[0]
    ar = jax.lax.broadcasted_iota(jnp.int32, (bq, k), 1)

    def cond(carry):
        return jnp.any(carry[0] != -1)

    def body(carry):
        node, d2s, idxs = carry                    # (bq,), (bq, k), (bq, k)
        active = node != -1
        nd = jnp.where(active, node, 0)

        lo = _take(node_lo, nd)
        hi = _take(node_hi, nd)
        g = jnp.maximum(jnp.maximum(lo - qc, qc - hi), 0.0)
        d2 = jnp.sum(g * g, axis=1)                # point-to-box, squared
        tau2 = d2s[:, k - 1]
        promising = (d2 < tau2) & active           # strict, like _knn_one

        is_leaf = nd >= n - 1
        leaf_pos = jnp.clip(nd - (n - 1), 0, n - 1)
        orig = _take(perm, leaf_pos)
        ok = is_leaf & promising                   # leaf box distance IS the
                                                   # fine distance here
        # branch-free sorted insert of (d2, orig) into the candidate lists
        pos = jnp.sum(d2s < d2[:, None], axis=1)   # (bq,) insertion point
        shift_d = jnp.concatenate([d2[:, None], d2s[:, :-1]], axis=1)
        shift_i = jnp.concatenate([orig[:, None], idxs[:, :-1]], axis=1)
        at = pos[:, None]
        new_d = jnp.where(ar < at, d2s, jnp.where(ar == at, d2[:, None], shift_d))
        new_i = jnp.where(ar < at, idxs, jnp.where(ar == at, orig[:, None], shift_i))
        d2s = jnp.where(ok[:, None], new_d, d2s)
        idxs = jnp.where(ok[:, None], new_i, idxs)

        descend = promising & ~is_leaf
        nxt = jnp.where(descend, _take(left, jnp.minimum(nd, n - 2)),
                        _take(rope, nd))
        return jnp.where(active, nxt, -1), d2s, idxs

    node0 = jnp.zeros((bq,), jnp.int32)
    d0 = jnp.full((bq, k), jnp.inf, jnp.float32)
    i0 = jnp.full((bq, k), -1, jnp.int32)
    _, d2s, idxs = jax.lax.while_loop(cond, body, (node0, d0, i0))
    dist_ref[...] = jnp.sqrt(d2s)
    idx_ref[...] = idxs


def bvh_traverse_knn(node_lo, node_hi, rope, left_child, leaf_perm, queries,
                     *, k: int, bq: int = 256, interpret: bool | None = None):
    """Fused stackless k-nearest traversal for (Q, dim) query points.

    Returns (dists, idxs): (Q, k) float32/int32, ascending, padded with
    (inf, -1) when fewer than k leaves are reachable.
    """
    dists, idxs = _bvh_traverse_knn_jit(
        node_lo, node_hi, rope, left_child, leaf_perm, queries, k=k, bq=bq,
        interpret=interpret)
    sanitize.check_knn(dists, idxs, n=leaf_perm.shape[0],
                       kernel="bvh_traverse_knn")
    return dists, idxs


@functools.partial(jax.jit, static_argnames=("k", "bq", "interpret"))
def _bvh_traverse_knn_jit(node_lo, node_hi, rope, left_child, leaf_perm,
                          queries, *, k: int, bq: int = 256,
                          interpret: bool | None = None):
    if interpret is None:
        interpret = sanitize.interpret_default()
    q, dim = queries.shape
    n = leaf_perm.shape[0]
    if q == 0:
        return (jnp.zeros((0, k), jnp.float32), jnp.zeros((0, k), jnp.int32))
    dim_p = _round_up(dim, 8)
    bq_eff = min(bq, _round_up(q, 8))
    qp = _round_up(q, bq_eff)

    qc = _pad_cols(_pad_rows(queries.astype(jnp.float32), qp, jnp.inf), dim_p)
    nlo = _pad_cols(node_lo.astype(jnp.float32), dim_p)
    nhi = _pad_cols(node_hi.astype(jnp.float32), dim_p)

    m = nlo.shape[0]
    kernel = functools.partial(_knn_kernel, n=n, k=k)
    dists, idxs = pl.pallas_call(
        kernel,
        grid=(qp // bq_eff,),
        in_specs=[
            pl.BlockSpec((bq_eff, dim_p), lambda i: (i, 0)),
            pl.BlockSpec((m, dim_p), lambda i: (0, 0)),
            pl.BlockSpec((m, dim_p), lambda i: (0, 0)),
            pl.BlockSpec((m,), lambda i: (0,)),
            pl.BlockSpec((n - 1,), lambda i: (0,)),
            pl.BlockSpec((n,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((bq_eff, k), lambda i: (i, 0)),
            pl.BlockSpec((bq_eff, k), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((qp, k), jnp.float32),
            jax.ShapeDtypeStruct((qp, k), jnp.int32),
        ],
        compiler_params=compiler_params(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(qc, nlo, nhi, rope, left_child, leaf_perm)
    return dists[:q], idxs[:q]


# ---------------------------------------------------------------------------
# reprolint sanitizer specs (analysis/pallas_trace.py)
# ---------------------------------------------------------------------------

def _zeros_tree(n: int, dim: int):
    m = 2 * n - 1
    return (jnp.zeros((m, dim), jnp.float32), jnp.zeros((m, dim), jnp.float32),
            jnp.zeros((m,), jnp.int32), jnp.zeros((n - 1,), jnp.int32),
            jnp.zeros((m,), jnp.int32), jnp.zeros((n,), jnp.int32))


def REPROLINT_SPECS():
    """Worst-case launches the route table admits, for the PLK001/PLK002
    sanitizer. Thunks call the RAW (un-jitted) wrappers so the analyzer's
    pallas_call spy always fires. Lazy core import: kernels must not pull
    core in at module import time (engine -> kernels would cycle)."""
    from ..core.route_table import RouteTable

    table = RouteTable.default()

    def spatial():
        rule = table.rule("spatial")
        n = (rule.pallas_max_nodes + 1) // 2       # 2n-1 == pallas_max_nodes
        nlo, nhi, rope, left, rlast, perm = _zeros_tree(n, 8)
        q = rule.block_q
        _bvh_traverse_spatial_jit.__wrapped__(
            nlo, nhi, rope, left, rlast, perm,
            jnp.zeros((q, 8), jnp.float32), jnp.zeros((q, 8), jnp.float32),
            jnp.zeros((q,), jnp.float32), capacity=rule.pallas_max_capacity,
            fine_sqrt=True, bq=rule.block_q, interpret=True)

    def knn():
        rule = table.rule("knn")
        n = (rule.pallas_max_nodes + 1) // 2
        nlo, nhi, rope, left, _, perm = _zeros_tree(n, 8)
        q = rule.block_q
        _bvh_traverse_knn_jit.__wrapped__(
            nlo, nhi, rope, left, perm, jnp.zeros((q, 8), jnp.float32),
            k=rule.pallas_max_capacity, bq=rule.block_q, interpret=True)

    return [{"name": "spatial@route-limits", "call": spatial},
            {"name": "knn@route-limits", "call": knn}]

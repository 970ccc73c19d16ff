"""Fused LBVH construction kernels (ISSUE 7 tentpole; DESIGN.md §8).

The reference build in :mod:`repro.core.lbvh` spends ~90 % of its time in
``_karras_ranges``: three unrolled log-depth searches, each step evaluating
delta(i, j) from scratch — six N-wide gathers (hi/lo/idx at i and j) plus
xor/clz per probe. This module replaces that with two exact algebraic
rewrites that produce **bit-identical** topology and bounds (pinned
node-for-node by ``tests/test_build_conformance.py``):

1.  *Delta RMQ.* The 96-bit augmented keys (hi:32 | lo:32 | idx:32) are
    strictly increasing after the Morton sort, and for lexicographically
    sorted keys the common-prefix length satisfies

        delta(i, j) = min_{m in [min(i,j), max(i,j)-1]} delta(m, m+1)

    (the LCP of the extremes of a sorted range is the min of adjacent
    LCPs — exact equality, not a bound). So we precompute the (N-1,)
    adjacent deltas once, build an O(N log N) sparse min-table over them,
    and every delta evaluation becomes TWO flat gathers + a min.

2.  *Monotone binary search.* delta(i, i + l*d) is nonincreasing in l
    (widening a sorted range can only shorten the common prefix; out-of-
    range probes return -1, below every valid delta). Karras's exponential
    upper-bound search + bounded binary search + ceil-division split search
    all reduce to the same primitive — "largest m with F(m) > threshold"
    for a monotone predicate — which ONE descending power-of-two pass
    computes exactly. Greedy descent over 2^K..1 reaches any target in
    [0, 2^(K+1)-1] exactly (binary representation), and the ceil-division
    t-sequence of the reference reaches the same unique maximum, so the
    resulting (first, last, gamma) integers are identical.

The AABB reduce keeps the reference's RMQ-sparse-table math but flattens
the (L, N, 2*dim) table to rows gathered at ``k*N + first`` — one flat
index vector instead of a two-level fancy gather (the other profiled
hotspot). Same float min ops in the same order: identical bounds.

A Pallas kernel (`karras_ranges_pallas`) spells the same two searches
with direct xor/clz delta evaluation against the key arrays staged whole
in VMEM (3 int32 tables, 12 B/leaf, declared up to
:data:`PALLAS_MAX_LEAVES` leaves), a block of internal nodes per grid
cell. It is OFF the TPU route: Mosaic cannot lower its per-lane 1-D key
gathers (:data:`PALLAS_TPU_REASON`). It runs in interpret mode only, as
the REPRO_SANITIZE twin check; `karras_ranges` serves every build, on
every backend, with the jit twin.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core import morton as M
from . import sanitize
from ._compat import compiler_params
from .ops import _round_up

__all__ = ["karras_ranges", "karras_ranges_fused", "karras_ranges_pallas",
           "aabb_rmq", "PALLAS_MAX_LEAVES", "PALLAS_TPU_REASON"]

_BIG = jnp.iinfo(jnp.int32).max      # plain int: safe to bake in under jit

#: largest build the Pallas ranges kernel is declared for: its three
#: whole-array key tables (12 B/leaf) must fit VMEM next to the output
#: blocks, so 2^20 leaves (12 MB) is the ceiling — a 2^22-leaf build
#: (48 MB of keys) cannot be staged at all
PALLAS_MAX_LEAVES = 1 << 20

#: why `karras_ranges` never picks the kernel on TPU (the TPU compiler's
#: own message; ``tests/test_tpu_compile.py`` holds it as a strict xfail)
PALLAS_TPU_REASON = (
    "karras_ranges_pallas does not compile for TPU: Mosaic rejects its "
    "1-D key gathers (jnp.take) with 'NotImplementedError: Only 2D gather "
    "is supported'")


# ---------------------------------------------------------------------------
# delta RMQ (rewrite 1)
# ---------------------------------------------------------------------------

def _delta_table(dadj, max_log2: int):
    """Sparse min-table over the (N-1,) adjacent deltas, flattened to
    (L*(N-1),) so lookups are single flat gathers. Row k entry m is
    min(dadj[m : m+2^k]); entries whose window runs past the end carry
    +BIG padding contributions (never gathered by in-range queries)."""
    n1 = dadj.shape[0]
    levels = [dadj]
    for k in range(1, max_log2 + 1):
        h = 1 << (k - 1)
        prev = levels[-1]
        pad = jnp.full((min(h, n1),), _BIG, dadj.dtype)
        levels.append(jnp.minimum(prev, jnp.concatenate([prev[h:], pad])))
    return jnp.concatenate(levels)


def _rmq_delta(tbl_flat, n1: int, i, j):
    """delta(i, j) via the min-table; -1 when j is outside [0, n1]
    (n1 == N-1, the last valid key index). i, j int32 arrays; i != j
    guaranteed by the searches (every probe offset is >= 1)."""
    ok = (j >= 0) & (j <= n1)
    jc = jnp.clip(j, 0, n1)
    a = jnp.minimum(i, jc)
    b = jnp.maximum(i, jc)
    length = jnp.maximum(b - a, 1)          # window of adjacent deltas [a, b-1]
    k = 31 - M._clz32(length.astype(jnp.uint32))
    lo = jnp.take(tbl_flat, k * n1 + a, mode="clip")
    hi = jnp.take(tbl_flat, k * n1 + (b - (jnp.int32(1) << k)), mode="clip")
    return jnp.where(ok, jnp.minimum(lo, hi), -1)


# ---------------------------------------------------------------------------
# the two monotone searches (rewrite 2)
# ---------------------------------------------------------------------------

def _descend_search(probe, threshold, max_log2: int, zero):
    """Largest m >= 0 with probe(m) > threshold, for nonincreasing probe.
    Descending power-of-two greedy: exact for any maximum < 2^(max_log2+1)."""
    m = zero
    for k in range(max_log2, -1, -1):
        t = jnp.int32(1 << k)
        m = jnp.where(probe(m + t) > threshold, m + t, m)
    return m


def karras_ranges_fused(hi, lo, idx, n: int, max_log2: int):
    """jit twin of the reference ``_karras_ranges``: identical (first,
    last, gamma) int32 triples, ~4x fewer N-wide gathers per build."""
    n1 = n - 1
    dadj = M.delta_from_keys(hi, lo, idx).astype(jnp.int32)
    tbl = _delta_table(dadj, max_log2)

    i = jnp.arange(n1, dtype=jnp.int32)
    d_r = dadj                                           # delta(i, i+1)
    d_l = jnp.concatenate([jnp.full((1,), -1, jnp.int32), dadj[:-1]])
    d = jnp.where(d_r > d_l, jnp.int32(1), jnp.int32(-1))
    delta_min = jnp.where(d > 0, d_l, d_r)

    delta = lambda j: _rmq_delta(tbl, n1, i, j)
    zero = jnp.zeros_like(i)

    l = _descend_search(lambda m: delta(i + m * d), delta_min, max_log2, zero)
    j = i + l * d
    first = jnp.minimum(i, j)
    last = jnp.maximum(i, j)

    delta_node = delta(j)
    s = _descend_search(lambda m: delta(i + m * d), delta_node, max_log2, zero)
    gamma = i + s * d + jnp.minimum(d, 0)
    return first, last, gamma


# ---------------------------------------------------------------------------
# Pallas TPU kernel: same searches, keys staged whole in VMEM
# ---------------------------------------------------------------------------

def _karras_kernel(hi_ref, lo_ref, idx_ref, first_ref, last_ref, gamma_ref,
                   *, n: int, max_log2: int, bn: int):
    hi = hi_ref[...]                                     # (n,) int32 bit-lanes
    lo = lo_ref[...]
    idx = idx_ref[...]
    blk = pl.program_id(0)
    i = blk * bn + jax.lax.broadcasted_iota(jnp.int32, (bn,), 0)
    i = jnp.minimum(i, n - 2)          # padded lanes recompute node n-2

    def delta(j):
        # direct 96-bit xor/clz — the tree stays in registers/VMEM, so the
        # six gathers per probe are cheap here (unlike the HBM jit path)
        ok = (j >= 0) & (j <= n - 1)
        jc = jnp.clip(j, 0, n - 1)
        hx = jnp.take(hi, i, mode="clip") ^ jnp.take(hi, jc, mode="clip")
        lx = jnp.take(lo, i, mode="clip") ^ jnp.take(lo, jc, mode="clip")
        ix = jnp.take(idx, i, mode="clip") ^ jnp.take(idx, jc, mode="clip")
        dd = jnp.where(hx != 0, jax.lax.clz(hx),
                       jnp.where(lx != 0, 32 + jax.lax.clz(lx),
                                 64 + jax.lax.clz(ix)))
        return jnp.where(ok, dd, -1)

    d_r = delta(i + 1)
    d_l = delta(i - 1)
    d = jnp.where(d_r > d_l, jnp.int32(1), jnp.int32(-1))
    delta_min = jnp.where(d > 0, d_l, d_r)

    l = jnp.zeros_like(i)
    for k in range(max_log2, -1, -1):
        t = jnp.int32(1 << k)
        l = jnp.where(delta(i + (l + t) * d) > delta_min, l + t, l)
    j = i + l * d
    first_ref[...] = jnp.minimum(i, j)
    last_ref[...] = jnp.maximum(i, j)

    delta_node = delta(j)
    s = jnp.zeros_like(i)
    for k in range(max_log2, -1, -1):
        t = jnp.int32(1 << k)
        s = jnp.where(delta(i + (s + t) * d) > delta_node, s + t, s)
    gamma_ref[...] = i + s * d + jnp.minimum(d, 0)


def karras_ranges_pallas(hi, lo, idx, n: int, max_log2: int, *,
                         bn: int = 512, interpret: bool | None = None):
    """Pallas spelling of :func:`karras_ranges_fused` (bit-identical ints),
    declared for at most :data:`PALLAS_MAX_LEAVES` leaves."""
    if n > PALLAS_MAX_LEAVES:
        raise ValueError(f"karras_ranges_pallas is declared for at most "
                         f"{PALLAS_MAX_LEAVES} leaves (got {n}): the key "
                         "tables would not fit VMEM")
    if interpret is None:
        interpret = sanitize.interpret_default()
    n1 = n - 1
    bn_eff = min(bn, _round_up(n1, 8))
    np_ = _round_up(n1, bn_eff)
    as_i32 = lambda a: jax.lax.bitcast_convert_type(a, jnp.int32)
    kernel = functools.partial(_karras_kernel, n=n, max_log2=max_log2,
                               bn=bn_eff)
    first, last, gamma = pl.pallas_call(
        kernel,
        grid=(np_ // bn_eff,),
        in_specs=[pl.BlockSpec((n,), lambda i: (0,))] * 3,
        out_specs=[pl.BlockSpec((bn_eff,), lambda i: (i,))] * 3,
        out_shape=[jax.ShapeDtypeStruct((np_,), jnp.int32)] * 3,
        compiler_params=compiler_params(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(as_i32(hi), as_i32(lo), as_i32(idx))
    return first[:n1], last[:n1], gamma[:n1]


def karras_ranges(hi, lo, idx, n: int, max_log2: int):
    """The build's range search: the delta-RMQ jit twin on every backend
    (the kernel is off the TPU route, :data:`PALLAS_TPU_REASON`, and
    interpret mode would simulate it op-by-op elsewhere). Under
    REPRO_SANITIZE on concrete inputs the kernel runs too and both must
    agree bit-for-bit — the build-conformance invariant, checked live."""
    if sanitize.enabled() and sanitize.is_concrete(hi, lo, idx):
        pk = karras_ranges_pallas(hi, lo, idx, n, max_log2)
        fk = karras_ranges_fused(hi, lo, idx, n, max_log2)
        for a, b in zip(pk, fk):
            if not bool(jnp.all(a == b)):
                raise AssertionError(
                    "REPRO_SANITIZE: karras_ranges_pallas disagrees with "
                    "karras_ranges_fused")
        for r, kern in ((pk, "karras_ranges_pallas"),
                        (fk, "karras_ranges_fused")):
            sanitize.check_karras(*r, n=n, kernel=kern)
        return fk
    return karras_ranges_fused(hi, lo, idx, n, max_log2)


# ---------------------------------------------------------------------------
# AABB reduce: flat-gather RMQ sparse table
# ---------------------------------------------------------------------------

def aabb_rmq(leaf_lo, leaf_hi, first, last, max_log2: int):
    """Internal AABBs over sorted leaf boxes — the RMQ sparse table of the
    reference ``_refit_rmq``, kept in its stacked (L, N, 2*dim) ``tbl[k,
    first]`` spelling: profiling showed XLA:CPU lowers the two-level fancy
    gather ~8x faster than a flattened row gather at ``k*N + first``, so
    the "flat" rewrite stays rejected. Same float min ops in the same
    order as the reference: bit-identical bounds."""
    dim = leaf_lo.shape[1]
    key = jnp.concatenate([leaf_lo, -leaf_hi], axis=1)    # (N, 2*dim)
    levels = [key]
    for k in range(1, max_log2 + 1):
        h = 1 << (k - 1)
        prev = levels[-1]
        pad = jnp.full((h, 2 * dim), jnp.inf, key.dtype)
        levels.append(jnp.minimum(prev, jnp.concatenate([prev[h:], pad], 0)))
    tbl = jnp.stack(levels)                               # (L, N, 2*dim)

    length = last - first + 1
    k = 31 - M._clz32(length.astype(jnp.uint32))          # floor(log2(len))
    off = last - (jnp.int32(1) << k) + 1
    combo = jnp.minimum(tbl[k, first], tbl[k, off])
    return combo[:, :dim], -combo[:, dim:]


# ---------------------------------------------------------------------------
# reprolint sanitizer spec (analysis/pallas_trace.py)
# ---------------------------------------------------------------------------

def REPROLINT_SPECS():
    def ranges_launch():
        n = PALLAS_MAX_LEAVES
        z = jnp.zeros((n,), jnp.uint32)
        karras_ranges_pallas(z, z, z, n, max_log2=20, interpret=True)

    return [{"name": "karras-ranges@max-leaves", "call": ranges_launch}]

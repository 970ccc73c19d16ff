"""LBVH construction (§2.6).

ArborX 2.0 on GPU: 64-bit Morton + Apetrei's agglomerative bottom-up build
(atomics) + stackless-traversal ropes (Prokopenko & Lebrun-Grandié 2024).

TPU adaptation (see DESIGN.md §2): no device-wide atomics in the XLA/Pallas
programming model, so we build functionally:

  1. Morton sort            -> jax.lax.sort (multi-key for 64-bit codes)
  2. node *ranges*          -> Karras-style parallel binary search over deltas
  3. parent/child *linking* -> O(1) per node from ranges + split (Apetrei's
                               insight that linking needs no extra search)
  4. AABB refit             -> **RMQ sparse-table** over sorted leaf boxes
                               (internal box == per-dim min/max over the leaf
                               range — a range-min query, O(N log N) fully
                               parallel, no atomics and no level sync), or an
                               iterative readiness fixpoint for huge N
  5. ropes                  -> closed form: rope(node covering [f,l]) =
                               right_child(split_owner(l)); split positions
                               are a bijection so this is one scatter+gather.
  6. node table             -> every node's box, rope, range_last and child
                               packed as one int32 record, records in
                               lane-dense 128-lane rows: the traversal
                               loop's one gather a step (``node_record``).

Node numbering: internal 0..N-2 (root = 0), leaves N-1..2N-2
(leaf node id = N-1 + sorted position). SENTINEL rope = -1.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from . import morton as M
from .geometry import Boxes

__all__ = ["LBVH", "build", "node_record", "node_record_lanes", "refit",
           "refit_with_quality", "sah_cost"]

BUILD_ENGINES = ("auto", "pallas", "ref")

SENTINEL = jnp.int32(-1)

ROW_LANES = 128     # a TPU vector register row: the node table's minor width


def _register(cls):
    cls = dataclasses.dataclass(frozen=True)(cls)
    fields = [f.name for f in dataclasses.fields(cls)]
    jax.tree_util.register_pytree_node(
        cls,
        lambda obj: (tuple(getattr(obj, f) for f in fields), None),
        lambda aux, children: cls(*children),
    )
    return cls


@_register
class LBVH:
    """Flat LBVH. All arrays are device arrays; the structure is a pytree so
    it can cross jit/shard_map boundaries."""
    node_lo: jax.Array      # (2N-1, dim) node AABB mins   (internal | leaves)
    node_hi: jax.Array      # (2N-1, dim)
    left_child: jax.Array   # (N-1,) int32 node ids
    right_child: jax.Array  # (N-1,)
    rope: jax.Array         # (2N-1,) int32 escape pointers (stackless, -1 = done)
    range_last: jax.Array   # (2N-1,) int32 last sorted-leaf position in subtree
    leaf_perm: jax.Array    # (N,) int32: sorted leaf position -> original index
    range_first: jax.Array  # (N-1,) int32 first sorted-leaf position per
                            # internal node — kept so ``refit`` can re-run the
                            # RMQ AABB pass without redoing the Karras search
    node_table: jax.Array   # (rows, max(128, L)) int32: every node's record
                            # of L lanes (see ``node_record``), what the
                            # traversal loop reads — one row gather a step

    @property
    def num_leaves(self):
        return self.leaf_perm.shape[0]

    @property
    def dim(self):
        return self.node_lo.shape[-1]


def node_record_lanes(dim: int) -> int:
    """Lanes of one node record: the smallest power of two >= 2*dim + 3
    (lo bits, hi bits, rope, range_last, child). 16 for 3-D boxes."""
    return 1 << (2 * dim + 2).bit_length()


def _box_bits(x):
    """Float box coordinates -> their bit patterns as int32 lanes (exact:
    -0.0, infinities and NaN payloads survive the round trip)."""
    if x.dtype.itemsize == 4:
        return jax.lax.bitcast_convert_type(x, jnp.int32)
    if x.dtype.itemsize > 4:
        raise ValueError(f"node boxes of {x.dtype} do not fit int32 lanes")
    uint = jnp.dtype(f"uint{8 * x.dtype.itemsize}")
    return jax.lax.bitcast_convert_type(x, uint).astype(jnp.int32)


def _box_from_bits(v, dtype):
    if jnp.dtype(dtype).itemsize == 4:
        return jax.lax.bitcast_convert_type(v, dtype)
    uint = jnp.dtype(f"uint{8 * jnp.dtype(dtype).itemsize}")
    return jax.lax.bitcast_convert_type(v.astype(uint), dtype)


def _pack_nodes(node_lo, node_hi, rope, range_last, left_child, leaf_perm):
    """The node table: ``rows = ceil(M / P)`` rows of ``P * L`` int32 lanes,
    P = max(1, 128 // L) records to a row. Node i's record is lanes
    [s*L, (s+1)*L) of row i % rows, s = i // rows: lo and hi (float bits),
    rope, range_last and the child (``left_child`` for internal nodes,
    ``leaf_perm`` for leaves). Rows are lane-dense, so the loop gathers
    whole rows in the layout they are stored in; records are dealt to rows
    in blocks of ``rows`` nodes so that packing is one 2-D transpose of a
    field-major array, lane-dense on both sides (DESIGN.md §2)."""
    m, dim = node_lo.shape
    lanes = node_record_lanes(dim)
    per_row = max(1, ROW_LANES // lanes)
    rows = -(-m // per_row)
    child = jnp.concatenate([left_child, leaf_perm])
    fields = jnp.concatenate([
        _box_bits(node_lo).T, _box_bits(node_hi).T,
        jnp.stack([rope, range_last, child]),
        jnp.zeros((lanes - 2 * dim - 3, m), jnp.int32)])      # (L, M)
    fields = jnp.pad(fields, ((0, 0), (0, rows * per_row - m)))
    return (fields.reshape(lanes, per_row, rows).transpose(1, 0, 2)
            .reshape(per_row * lanes, rows).T)


def node_record(tree: LBVH, node):
    """Read node ``node``'s record: ``(lo, hi, rope, range_last, child)``.

    One row gather from ``tree.node_table``; the record's lanes are picked
    from the row by an exact integer select (every other record masked to
    0 and summed away) and the box lanes bitcast back to the boxes' dtype,
    so the values are bit-identical to ``node_lo[node]`` etc."""
    dim = tree.dim
    lanes = node_record_lanes(dim)
    rows, width = tree.node_table.shape
    per_row = width // lanes
    row = tree.node_table[node % rows]
    if per_row > 1:
        mine = jnp.arange(per_row)[:, None] == node // rows
        row = jnp.where(mine, row.reshape(per_row, lanes), 0).sum(
            0, dtype=row.dtype)
    dtype = tree.node_lo.dtype
    return (_box_from_bits(row[:dim], dtype),
            _box_from_bits(row[dim:2 * dim], dtype),
            row[2 * dim], row[2 * dim + 1], row[2 * dim + 2])


def _dkey(hi, lo, idx, i, j, n):
    """delta(i, j) = common-prefix length of 96-bit augmented keys, -1 when
    j outside [0, n-1]. i, j: int32 arrays of equal shape."""
    j_ok = (j >= 0) & (j <= n - 1)
    jc = jnp.clip(j, 0, n - 1)
    hx = hi[i] ^ hi[jc]
    lx = lo[i] ^ lo[jc]
    ix = idx[i] ^ idx[jc]
    d_hi = M._clz32(hx)
    d_lo = 32 + M._clz32(lx)
    d_ix = 64 + M._clz32(ix)
    d = jnp.where(hx != 0, d_hi, jnp.where(lx != 0, d_lo, d_ix))
    return jnp.where(j_ok, d, -1)


def _karras_ranges(hi, lo, idx, n: int, max_log2: int):
    """Vectorized Karras range+split computation for all internal nodes.

    Returns (first, last, gamma): (N-1,) int32 each. All searches run as
    unrolled log2(N) passes of vector-wide gathers (VPU-friendly)."""
    i = jnp.arange(n - 1, dtype=jnp.int32)
    d_r = _dkey(hi, lo, idx, i, i + 1, n)
    d_l = _dkey(hi, lo, idx, i, i - 1, n)
    d = jnp.where(d_r > d_l, jnp.int32(1), jnp.int32(-1))
    delta_min = jnp.where(d > 0, d_l, d_r)

    # upper bound for range length: exponential search
    l_max = jnp.full_like(i, 2)
    for _ in range(max_log2 + 1):
        cond = _dkey(hi, lo, idx, i, i + l_max * d, n) > delta_min
        l_max = jnp.where(cond, l_max * 2, l_max)

    # binary search for exact length l
    l = jnp.zeros_like(i)
    t = l_max // 2
    for _ in range(max_log2 + 1):
        cond = (t >= 1) & (_dkey(hi, lo, idx, i, i + (l + t) * d, n) > delta_min)
        l = jnp.where(cond, l + t, l)
        t = t // 2
    j = i + l * d
    first = jnp.minimum(i, j)
    last = jnp.maximum(i, j)

    # split search: largest s with delta(i, i + (s+t)*d) > delta_node
    delta_node = _dkey(hi, lo, idx, i, j, n)
    s = jnp.zeros_like(i)
    div = jnp.full_like(i, 2)
    for _ in range(max_log2 + 1):
        t = (l + div - 1) // div      # ceil(l / div)
        cond = (t >= 1) & (_dkey(hi, lo, idx, i, i + (s + t) * d, n) > delta_node)
        s = jnp.where(cond, s + t, s)
        div = div * 2
    gamma = i + s * d + jnp.minimum(d, 0)
    return first, last, gamma


def _refit_rmq(leaf_lo, leaf_hi, first, last, max_log2: int):
    """Internal AABBs via a range-min sparse table over sorted leaf boxes.

    Beyond-paper TPU optimization: replaces ArborX's atomic-gated bottom-up
    refit with one O(N log N) prefix table + one gather per node. The hi
    bound rides in the same table negated (max(x) == -min(-x), exact in
    IEEE), halving the table-build passes — this is also the whole of
    ``refit``'s work between time steps, so it is the serving hot path.
    """
    dim = leaf_lo.shape[1]
    key = jnp.concatenate([leaf_lo, -leaf_hi], axis=1)    # (N, 2*dim)
    levels = [key]
    for k in range(1, max_log2 + 1):
        h = 1 << (k - 1)
        prev = levels[-1]
        # min(prev[i], prev[i+h]) with +inf padding past the end
        pad = jnp.full((h, 2 * dim), jnp.inf, key.dtype)
        levels.append(jnp.minimum(prev, jnp.concatenate([prev[h:], pad], 0)))
    tbl = jnp.stack(levels)                               # (L, N, 2*dim)

    length = last - first + 1
    k = 31 - M._clz32(length.astype(jnp.uint32))          # floor(log2(len))
    off = last - (jnp.int32(1) << k) + 1
    combo = jnp.minimum(tbl[k, first], tbl[k, off])
    return combo[:, :dim], -combo[:, dim:]


def _refit_iterative(leaf_lo, leaf_hi, left_child, right_child):
    """Readiness-fixpoint refit: O(tree-height) masked passes. Used when the
    sparse table would not fit memory (N > ~2^21)."""
    n = leaf_lo.shape[0]
    ni = n - 1
    node_lo = jnp.concatenate([jnp.full((ni, leaf_lo.shape[1]), jnp.inf, leaf_lo.dtype), leaf_lo])
    node_hi = jnp.concatenate([jnp.full((ni, leaf_hi.shape[1]), -jnp.inf, leaf_hi.dtype), leaf_hi])
    ready = jnp.concatenate([jnp.zeros((ni,), bool), jnp.ones((n,), bool)])

    def cond(c):
        _, _, ready = c
        return ~jnp.all(ready[:ni])

    def body(c):
        node_lo, node_hi, ready = c
        lr, rr = ready[left_child], ready[right_child]
        can = lr & rr & ~ready[:ni]
        new_lo = jnp.minimum(node_lo[left_child], node_lo[right_child])
        new_hi = jnp.maximum(node_hi[left_child], node_hi[right_child])
        node_lo = node_lo.at[:ni].set(jnp.where(can[:, None], new_lo, node_lo[:ni]))
        node_hi = node_hi.at[:ni].set(jnp.where(can[:, None], new_hi, node_hi[:ni]))
        ready = ready.at[:ni].set(ready[:ni] | can)
        return node_lo, node_hi, ready

    node_lo, node_hi, _ = jax.lax.while_loop(cond, body, (node_lo, node_hi, ready))
    return node_lo[:ni], node_hi[:ni]


def _resolve_build_engine(engine: str) -> str:
    """Resolve the build-engine selector to "pallas" (the fused delta-RMQ
    build of ``kernels.lbvh_build``, a jit program on every backend) or
    "ref" (the original unfused searches). "auto" is the fused build: it
    is exact, so it is safe to prefer everywhere.
    """
    if engine not in BUILD_ENGINES:
        raise ValueError(f"engine={engine!r} is not one of {BUILD_ENGINES}")
    return "pallas" if engine == "auto" else engine


def build(boxes: Boxes, *, bits: int = 64, refit: str = "rmq",
          engine: str = "auto") -> LBVH:
    """Build an LBVH over N >= 2 leaf boxes.

    bits: 32 or 64 (Morton code width, §2.6 — 64 is the 2.0 default).
    refit: "rmq" (sparse table) or "iterative" (readiness fixpoint).
    engine: "pallas" (fused delta-RMQ build, ``kernels.lbvh_build``),
        "ref" (the original Karras searches), or "auto" (the fused
        build). Both engines produce bit-identical trees — topology AND
        bounds.
    """
    return _build_impl(boxes, bits=bits, refit=refit,
                       engine=_resolve_build_engine(engine))


@partial(jax.jit, static_argnames=("bits", "refit", "engine"))
def _build_impl(boxes: Boxes, *, bits: int, refit: str, engine: str) -> LBVH:
    leaf_lo_u, leaf_hi_u = boxes.lo, boxes.hi
    n, dim = leaf_lo_u.shape
    if n < 2:
        raise ValueError("LBVH requires N >= 2 (BVH API handles N in {0,1})")
    max_log2 = max((n - 1).bit_length(), 1)

    centroids = 0.5 * (leaf_lo_u + leaf_hi_u)
    scene_lo, scene_hi = centroids.min(0), centroids.max(0)
    if bits == 64:
        codes = M.morton64(centroids, scene_lo, scene_hi)
    else:
        codes = M.morton32(centroids, scene_lo, scene_hi)
    perm0 = jnp.arange(n, dtype=jnp.int32)
    codes_s, perm = M.sort_by_morton(codes, perm0)
    hi, lo, idx = M.combined_delta_key(codes_s, n)

    leaf_lo = leaf_lo_u[perm]
    leaf_hi = leaf_hi_u[perm]

    if engine == "pallas":
        from ..kernels import lbvh_build as K
        first, last, gamma = K.karras_ranges(hi, lo, idx, n, max_log2)
    else:
        first, last, gamma = _karras_ranges(hi, lo, idx, n, max_log2)

    # Apetrei-style O(1) linking from ranges+split: child at gamma / gamma+1
    # is a leaf exactly when it coincides with the range end.
    left_child = jnp.where(gamma == first, (n - 1) + gamma, gamma).astype(jnp.int32)
    right_child = jnp.where(gamma + 1 == last, (n - 1) + gamma + 1, gamma + 1).astype(jnp.int32)

    if refit != "rmq":
        int_lo, int_hi = _refit_iterative(leaf_lo, leaf_hi, left_child, right_child)
    elif engine == "pallas":
        from ..kernels import lbvh_build as K
        int_lo, int_hi = K.aabb_rmq(leaf_lo, leaf_hi, first, last, max_log2)
    else:
        int_lo, int_hi = _refit_rmq(leaf_lo, leaf_hi, first, last, max_log2)
    node_lo = jnp.concatenate([int_lo, leaf_lo], 0)
    node_hi = jnp.concatenate([int_hi, leaf_hi], 0)

    # ropes in closed form: split positions gamma are a bijection onto
    # [0, N-2]; the node after subtree [f, l] is right_child(owner(l)).
    split_owner = jnp.zeros((n - 1,), jnp.int32).at[gamma].set(jnp.arange(n - 1, dtype=jnp.int32))
    leaf_pos = jnp.arange(n, dtype=jnp.int32)
    range_last = jnp.concatenate([last, leaf_pos]).astype(jnp.int32)
    safe_last = jnp.clip(range_last, 0, n - 2)
    rope = jnp.where(range_last >= n - 1, SENTINEL,
                     right_child[split_owner[safe_last]]).astype(jnp.int32)

    perm = perm.astype(jnp.int32)
    return LBVH(node_lo, node_hi, left_child, right_child, rope,
                range_last, perm, first.astype(jnp.int32),
                _pack_nodes(node_lo, node_hi, rope, range_last, left_child,
                            perm))


def _with_boxes(tree: LBVH, node_lo, node_hi) -> LBVH:
    """``tree`` with new node boxes and the node table packed from them."""
    return dataclasses.replace(
        tree, node_lo=node_lo, node_hi=node_hi,
        node_table=_pack_nodes(node_lo, node_hi, tree.rope, tree.range_last,
                               tree.left_child, tree.leaf_perm))


@jax.jit
def refit(tree: LBVH, boxes: Boxes) -> LBVH:
    """Recompute all AABBs for new leaf boxes, reusing the existing topology.

    The Karras ranges, Apetrei links, and ropes are functions of the Morton
    *order* only — they are coordinate-free. As long as the leaves keep their
    identity (same N, boxes indexed like the build input), moving the
    coordinates only invalidates the AABBs, which one RMQ pass recomputes.
    No sort, no range search: this is the fast path between simulation time
    steps (Prokopenko et al. 2024). Quality degrades as points drift from the
    build-time Morton order; monitor with :func:`sah_cost` and rebuild past a
    threshold (``service.IndexStore`` automates this).

    `boxes` are in ORIGINAL index order, exactly like the ``build`` input.
    """
    n = tree.num_leaves
    if boxes.lo.shape[0] != n:
        raise ValueError(f"refit needs the same leaf count (tree has {n}, "
                         f"got {boxes.lo.shape[0]}); rebuild instead")
    max_log2 = max((n - 1).bit_length(), 1)
    leaf_lo = boxes.lo[tree.leaf_perm]
    leaf_hi = boxes.hi[tree.leaf_perm]
    int_lo, int_hi = _refit_rmq(leaf_lo, leaf_hi, tree.range_first,
                                tree.range_last[:n - 1], max_log2)
    return _with_boxes(tree, jnp.concatenate([int_lo, leaf_lo], 0),
                       jnp.concatenate([int_hi, leaf_hi], 0))


@jax.jit
def refit_with_quality(tree: LBVH, boxes: Boxes) -> tuple[LBVH, jax.Array]:
    """Refit AND measure in one pass: returns ``(refitted_tree, sah)``.

    The shard-local refit entry for distributed serving (DESIGN.md §11):
    under ``shard_map`` every shard refits its local tree and reports its
    own SAH cost without a second sweep over the node arrays — the
    internal boxes feeding :func:`_surface_measure` are the ones the RMQ
    pass just produced. Semantics match ``refit`` + ``sah_cost`` exactly.
    """
    n = tree.num_leaves
    if boxes.lo.shape[0] != n:
        raise ValueError(f"refit needs the same leaf count (tree has {n}, "
                         f"got {boxes.lo.shape[0]}); rebuild instead")
    max_log2 = max((n - 1).bit_length(), 1)
    leaf_lo = boxes.lo[tree.leaf_perm]
    leaf_hi = boxes.hi[tree.leaf_perm]
    int_lo, int_hi = _refit_rmq(leaf_lo, leaf_hi, tree.range_first,
                                tree.range_last[:n - 1], max_log2)
    areas = _surface_measure(int_lo, int_hi)
    sah = jnp.sum(areas) / jnp.maximum(areas[0], jnp.finfo(areas.dtype).tiny)
    new = _with_boxes(tree, jnp.concatenate([int_lo, leaf_lo], 0),
                      jnp.concatenate([int_hi, leaf_hi], 0))
    return new, sah


def _surface_measure(lo, hi):
    """(M,) dimension-generic surface measure: sum over faces of the product
    of the other extents (2D: perimeter/2, 3D: surface area/2). 1D uses the
    interval length (hit probability is proportional to length, and a
    constant would make the drift monitor inert)."""
    e = jnp.maximum(hi - lo, 0.0)
    d = e.shape[-1]
    if d == 1:
        return e[..., 0]
    total = jnp.zeros(e.shape[:-1], e.dtype)
    for i in range(d):
        keep = jnp.arange(d) != i
        total = total + jnp.prod(jnp.where(keep, e, 1.0), axis=-1)
    return total


@jax.jit
def sah_cost(tree: LBVH) -> jax.Array:
    """SAH-style tree quality: sum of internal-node surface measures over the
    root's (expected traversal cost up to constants; Goldsmith & Salmon 1987).
    Lower is better. Refit preserves topology, so drifting points inflate
    internal boxes and this ratio grows — the rebuild trigger."""
    n = tree.num_leaves
    areas = _surface_measure(tree.node_lo[:n - 1], tree.node_hi[:n - 1])
    return jnp.sum(areas) / jnp.maximum(areas[0], jnp.finfo(areas.dtype).tiny)

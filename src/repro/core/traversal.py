"""Stackless BVH traversal (§2.6: rope-based, Prokopenko & Lebrun-Grandié
2024) with functional callbacks (§2.2) and early termination.

Per-query state is a single int32 node cursor — no stack — which is exactly
why this algorithm is the right one for SIMD/TPU: traversal is a vmapped
``lax.while_loop`` whose lanes are queries, all state lives in registers.

Callback protocol (the JAX spelling of ArborX's functor callbacks):

    callback(state, predicate, value, index, t) -> (new_state, done)

`state` is any pytree; `done=True` requests early termination of *this*
query's traversal (ArborX CallbackTreeTraversalControl). Traversal applies
callbacks unconditionally and masks the result, so user callbacks never see
masks. `index` is the ORIGINAL (pre-sort) position of the value; `t` is the
ray-hit parameter for ray predicates (0.0 for spatial ones).

The pair-traversal optimization (§2.6, Prokopenko et al. 2025) is exposed via
``min_pos``: subtrees whose last sorted-leaf position <= min_pos are skipped,
which turns a symmetric self-join into a strict upper-triangle traversal.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp

from . import geometry as G
from . import predicates as P
from .lbvh import LBVH, node_record

__all__ = ["traverse", "traverse_knn", "value_at"]


def value_at(values, i):
    """Gather element i of a pytree-of-arrays values container."""
    return jax.tree_util.tree_map(lambda a: a[i], values)


def _bmask(mask, a):
    """Broadcast scalar bool mask against array a."""
    return jnp.reshape(mask, (1,) * a.ndim) if a.ndim else mask


def tree_select(mask, new, old):
    return jax.tree_util.tree_map(lambda a, b: jnp.where(_bmask(mask, a), a, b), new, old)


def _traverse_one(tree: LBVH, values, pred, callback, state0, min_pos):
    """Traverse for a SINGLE (unbatched) predicate. Returns final state."""
    n = tree.num_leaves
    root = jnp.int32(0)

    def cond(carry):
        node, _, done = carry
        return (node != -1) & ~done

    def body(carry):
        node, state, done = carry
        is_leaf = node >= n - 1
        leaf_pos = node - (n - 1)
        lo, hi, rope, range_last, child = node_record(tree, node)

        # subtree pruning: geometric overlap + pair-traversal position filter
        overlap = P.node_overlap_test(pred, lo[None, :], hi[None, :])[0]
        pos_ok = range_last > min_pos
        descend = overlap & pos_ok & ~is_leaf

        # leaf handling: fine test (§2.1.2 "fine nearest/fine search") + callback
        orig_idx = jnp.where(is_leaf, child, 0)
        leaf_val = value_at(values, orig_idx)
        fine, cb_extra = _leaf_test(pred, leaf_val)
        hit = is_leaf & overlap & fine & (leaf_pos > min_pos)

        new_state, cb_done = callback(state, pred, leaf_val, orig_idx, cb_extra)
        state = tree_select(hit, new_state, state)
        done = done | (hit & cb_done)

        next_node = jnp.where(descend, child, rope)
        return next_node, state, done

    _, state, _ = jax.lax.while_loop(cond, body, (root, state0, jnp.bool_(False)))
    return state


def _as_batch1(val):
    return jax.tree_util.tree_map(lambda a: a[None], val)


def _leaf_test(pred, leaf_val):
    """Exact leaf-vs-predicate test. Returns (match: bool scalar, extra).

    extra is the hit parameter t for ray predicates (what ordered_intersect
    sorts by, §2.5) and 0.0 for spatial predicates; it is forwarded to
    callbacks as their 5th argument.
    """
    batched = _as_batch1(leaf_val)
    if isinstance(pred, (P.RayNearest, P.RayIntersect, P.RayOrderedIntersect)):
        hit, t = P.leaf_ray_hit(pred, batched)
        return jnp.reshape(hit, ()), jnp.reshape(t, ())
    fine = P.leaf_match_test(pred, batched)
    return jnp.reshape(fine, ()), jnp.float32(0.0)


@partial(jax.jit, static_argnames=("callback",))
def traverse(tree: LBVH, values, predicates, callback: Callable, state0, *,
             min_pos=None):
    """Batched spatial/ray traversal with callbacks.

    predicates: batched predicate pytree (N_q queries).
    state0: per-query initial state pytree WITH leading query axis, or
            unbatched (will be broadcast by vmap via in_axes=None? no —
            caller supplies batched state).
    min_pos: optional (N_q,) int32 for pair traversal; None disables.
    Returns final per-query states (leading axis N_q).
    """
    if min_pos is None:
        mp = jnp.full((len(predicates),), -1, jnp.int32)
    else:
        mp = min_pos

    def one(pred, st, m):
        return _traverse_one(tree, values, pred, callback, st, m)

    return jax.vmap(one, in_axes=(0, 0, 0))(predicates, state0, mp)


# ---------------------------------------------------------------------------
# k-nearest traversal: pruned rope-order walk with a fixed-size sorted
# candidate list (TPU adaptation of best-first heap traversal; see DESIGN.md)
# ---------------------------------------------------------------------------

def _insert_sorted(dists, idxs, d, i):
    """Insert (d, i) into the sorted-ascending candidate arrays (k,)."""
    k = dists.shape[0]
    pos = jnp.sum(dists < d)                       # insertion position
    ar = jnp.arange(k)
    shift_d = jnp.where(ar == 0, d, dists[jnp.maximum(ar - 1, 0)])
    shift_i = jnp.where(ar == 0, i, idxs[jnp.maximum(ar - 1, 0)])
    new_d = jnp.where(ar < pos, dists, jnp.where(ar == pos, d, shift_d))
    new_i = jnp.where(ar < pos, idxs, jnp.where(ar == pos, i, shift_i))
    take = pos < k
    return (jnp.where(take, new_d, dists), jnp.where(take, new_i, idxs))


def seed_window(k: int, n: int) -> int:
    """Sorted leaves the kNN seed reads: the power of two >= 3k, at most n.

    Three leaves for each neighbour sought keep the bound tight where the
    descent ends next to a jump of the Morton order (DESIGN.md §2 item 8)."""
    return min(1 << max(3 * k - 1, 0).bit_length(), n)


def _descend(tree: LBVH, pred, root):
    """One root-to-leaf path from the `root` record, into the child nearer
    the query (ties left). Returns (sorted leaf position, records read)."""
    n = tree.num_leaves

    def mind(lo, hi):
        return P.node_min_distance(pred, lo[None, :], hi[None, :])[0]

    def body(carry):
        _, left, reads = carry
        lo_l, hi_l, right, _, below_l = node_record(tree, left)
        lo_r, hi_r, _, _, below_r = node_record(tree, right)
        go_right = mind(lo_r, hi_r) < mind(lo_l, hi_l)
        return (jnp.where(go_right, right, left),
                jnp.where(go_right, below_r, below_l), reads + 2)

    node, _, reads = jax.lax.while_loop(
        lambda c: c[0] < n - 1, body, (jnp.int32(0), root[4], jnp.int32(1)))
    return node - (n - 1), reads


def _seed_bound(tree: LBVH, values, pred, k: int, exclude_label,
                leaf_labels):
    """An upper bound on the query's k-th neighbour distance, from the
    sorted leaves around the end of a one-path descent: the k-th smallest
    admissible seed distance, widened past any rounding gap between
    ``node_min_distance`` of a leaf's box and ``leaf_distance`` of its
    value; inf with fewer than k admissible seeds. Returns (bound, node
    records read)."""
    n = tree.num_leaves
    w = seed_window(k, n)
    if w < k:
        return jnp.asarray(jnp.inf, tree.node_lo.dtype), jnp.int32(0)
    root = node_record(tree, jnp.int32(0))
    pos, reads = _descend(tree, pred, root)
    start = jnp.clip(pos - w // 2, 0, n - w)
    idx = tree.leaf_perm[start + jnp.arange(w)]
    d = P.leaf_distance(pred, value_at(values, idx))
    big = jnp.asarray(jnp.inf, d.dtype)
    if leaf_labels is not None:
        d = jnp.where(leaf_labels[idx] == exclude_label, big, d)
    kth = jnp.sort(d)[k - 1]
    # The two formulas round apart by a share of the distance, which the
    # factor covers, and by a few ulps of the largest coordinate involved
    # (a sphere's box corner c + r, a segment's end rebuilt as a + (b - a)),
    # which the second term covers: 16 * dim ulps of the query's and the
    # tree's largest coordinate, whatever the distance.
    qbox = G.to_boxes(pred.geom)
    scale = (jnp.maximum(jnp.abs(root[0]), jnp.abs(root[1])).max()
             + jnp.maximum(jnp.abs(qbox.lo), jnp.abs(qbox.hi)).max())
    fi = jnp.finfo(d.dtype)
    grow = 1 + max(2.0 ** -10, 16 * float(fi.eps))
    ulps = 16 * tree.dim * float(fi.eps)
    bound = (kth * jnp.asarray(grow, d.dtype)
             + jnp.asarray(ulps, d.dtype) * scale.astype(d.dtype)
             + jnp.asarray(fi.tiny, d.dtype))
    return jnp.where(jnp.isfinite(kth), bound, big), reads


def _knn_one(tree: LBVH, values, pred, k: int, exclude_label, leaf_labels,
             count_steps: bool = False):
    """One query's walk. A spatial query first seeds its pruning bound
    from the leaves next to a one-path descent (``_seed_bound``); a ray's
    walk starts unbounded. With `count_steps` the carry holds one more
    int32, the nodes this query read (the descent's, then one a loop
    step), returned third, and whether the seed bound was finite fourth."""
    n = tree.num_leaves
    big = jnp.asarray(jnp.inf, tree.node_lo.dtype)
    if isinstance(pred, P.Nearest):
        tau_seed, reads = _seed_bound(tree, values, pred, k, exclude_label,
                                      leaf_labels)
    else:   # a Morton window of leaves bounds no ray's hits
        tau_seed, reads = big, jnp.int32(0)

    def cond(carry):
        return carry[0] != -1

    def body(carry):
        node, dists, idxs = carry[:3]
        tau = dists[k - 1]
        is_leaf = node >= n - 1
        lo, hi, rope, _, child = node_record(tree, node)
        mind = P.node_min_distance(pred, lo[None, :], hi[None, :])[0]
        promising = mind < jnp.minimum(tau, tau_seed)
        descend = promising & ~is_leaf

        orig_idx = jnp.where(is_leaf, child, 0)
        leaf_val = value_at(values, orig_idx)
        d = P.leaf_distance(pred, _as_batch1(leaf_val))[0]
        ok = is_leaf & promising & (d < tau)
        if leaf_labels is not None:
            ok = ok & (leaf_labels[orig_idx] != exclude_label)
        nd, ni = _insert_sorted(dists, idxs, d, orig_idx)
        dists2 = jnp.where(ok, nd, dists)
        idxs2 = jnp.where(ok, ni, idxs)

        next_node = jnp.where(descend, child, rope)
        return (next_node, dists2, idxs2) + tuple(s + 1 for s in carry[3:])

    dists0 = jnp.full((k,), big)
    idxs0 = jnp.full((k,), -1, jnp.int32)
    carry0 = (jnp.int32(0), dists0, idxs0)
    if not count_steps:
        return jax.lax.while_loop(cond, body, carry0)[1:]
    out = jax.lax.while_loop(cond, body, carry0 + (reads,))
    return out[1:] + (jnp.isfinite(tau_seed),)


@partial(jax.jit, static_argnames=("k", "count_steps"))
def traverse_knn(tree: LBVH, values, predicates, k: int, *,
                 exclude_labels=None, leaf_labels=None,
                 count_steps: bool = False):
    """Batched k-nearest traversal.

    Returns (dists, idxs): (N_q, k) each, padded with (inf, -1). Distances
    are FINE distances to the stored values (§2.1.2), not to leaf boxes.
    With `count_steps`, also (N_q,) int32, the nodes each query read (its
    seed descent's records plus its lane's loop steps), and (N_q,) bool,
    whether its pruning bound was seeded (a finite bound from k admissible
    leaves near it; DESIGN.md §2 item 8).

    exclude_labels/leaf_labels implement Borůvka's "nearest outside my
    component" query used by EMST (§2.4).
    """
    ex = exclude_labels if exclude_labels is not None else jnp.full((len(predicates),), -2, jnp.int32)

    def one(pred, e):
        return _knn_one(tree, values, pred, k, e, leaf_labels, count_steps)

    return jax.vmap(one, in_axes=(0, 0))(predicates, ex)

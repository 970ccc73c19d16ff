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


def _knn_one(tree: LBVH, values, pred, k: int, exclude_label, leaf_labels,
             count_steps: bool = False):
    """One query's walk. With `count_steps` the carry holds one more int32,
    the nodes this query visited (one a loop step), returned third."""
    n = tree.num_leaves
    big = jnp.asarray(jnp.inf, tree.node_lo.dtype)

    def cond(carry):
        return carry[0] != -1

    def body(carry):
        node, dists, idxs = carry[:3]
        tau = dists[k - 1]
        is_leaf = node >= n - 1
        lo, hi, rope, _, child = node_record(tree, node)
        mind = P.node_min_distance(pred, lo[None, :], hi[None, :])[0]
        promising = mind < tau
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
    if count_steps:
        carry0 += (jnp.int32(0),)
    return jax.lax.while_loop(cond, body, carry0)[1:]


@partial(jax.jit, static_argnames=("k", "count_steps"))
def traverse_knn(tree: LBVH, values, predicates, k: int, *,
                 exclude_labels=None, leaf_labels=None,
                 count_steps: bool = False):
    """Batched k-nearest traversal.

    Returns (dists, idxs): (N_q, k) each, padded with (inf, -1). Distances
    are FINE distances to the stored values (§2.1.2), not to leaf boxes.
    With `count_steps`, also (N_q,) int32: the nodes each query visited
    (its lane's loop steps; the batch's maximum is the loop's trip count).

    exclude_labels/leaf_labels implement Borůvka's "nearest outside my
    component" query used by EMST (§2.4).
    """
    ex = exclude_labels if exclude_labels is not None else jnp.full((len(predicates),), -2, jnp.int32)

    def one(pred, e):
        return _knn_one(tree, values, pred, k, e, leaf_labels, count_steps)

    return jax.vmap(one, in_axes=(0, 0))(predicates, ex)

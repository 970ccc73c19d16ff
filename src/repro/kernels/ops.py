"""Public jit'd wrappers for the Pallas kernels.

Each wrapper handles padding/alignment (lane width 128, sublane 8 — TPU
v5e tile shapes), dispatches to the Pallas kernel, and slices results
back. On CPU backends the kernels execute in interpret mode (the kernel
body runs as pure JAX) — identical semantics, which is what the tests
assert against ref.py.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import sanitize
from .bruteforce_knn import bruteforce_knn_pallas
from .flash_attention import flash_attention_pallas
from .morton import morton64_pallas
from .ray_box import ray_box_nearest_pallas

__all__ = ["morton64", "bruteforce_knn", "ray_box_nearest", "flash_attention"]


def _interpret() -> bool:
    # interpret off TPU, compiled on TPU (read at trace time — process-
    # stable; see kernels/sanitize.py)
    return sanitize.interpret_default()


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _pad_rows(a, n_to, fill=0.0):
    n = a.shape[0]
    if n == n_to:
        return a
    pad = jnp.full((n_to - n,) + a.shape[1:], fill, a.dtype)
    return jnp.concatenate([a, pad], 0)


def _pad_cols(a, d_to, fill=0.0):
    d = a.shape[1]
    if d == d_to:
        return a
    pad = jnp.full((a.shape[0], d_to - d), fill, a.dtype)
    return jnp.concatenate([a, pad], 1)


@partial(jax.jit, static_argnames=("bn",))
def morton64(coords, scene_lo=None, scene_hi=None, *, bn: int = 1024):
    """64-bit Morton codes of (N, dim) coords -> (hi, lo) uint32 (N,)."""
    n, dim = coords.shape
    if scene_lo is None:
        scene_lo = coords.min(0)
    if scene_hi is None:
        scene_hi = coords.max(0)
    bn_eff = min(bn, _round_up(n, 8))
    n_pad = _round_up(n, bn_eff)
    c = _pad_rows(coords, n_pad)
    hi, lo = morton64_pallas(c, scene_lo, scene_hi, bn=bn_eff,
                             interpret=_interpret())
    return hi[:n], lo[:n]


def bruteforce_knn(queries, points, k: int, *, bq: int = 256, bn: int = 512):
    """Exact kNN: (Q, dim) x (N, dim) -> (dists, idx) (Q, k) ascending."""
    d, i = _bruteforce_knn_jit(queries, points, k, bq=bq, bn=bn)
    sanitize.check_knn(d, i, n=points.shape[0], kernel="bruteforce_knn")
    return d, i


@partial(jax.jit, static_argnames=("k", "bq", "bn"))
def _bruteforce_knn_jit(queries, points, k: int, *, bq: int = 256,
                        bn: int = 512):
    q, dim = queries.shape
    n, _ = points.shape
    d_pad = _round_up(dim, 128)
    bq_eff = min(bq, _round_up(q, 8))
    bn_eff = min(bn, _round_up(n, 8))
    qq = _pad_cols(_pad_rows(queries, _round_up(q, bq_eff)), d_pad)
    pp = _pad_cols(_pad_rows(points, _round_up(n, bn_eff)), d_pad)
    d, i = bruteforce_knn_pallas(qq, pp, k, n_actual=n, bq=bq_eff,
                                 bn=bn_eff, interpret=_interpret())
    return d[:q], i[:q]


@partial(jax.jit, static_argnames=("br", "bb"))
def ray_box_nearest(origins, directions, box_lo, box_hi, *, br: int = 256,
                    bb: int = 512):
    """Nearest box per ray: returns (t, idx) (R,), t=inf/idx=-1 on miss."""
    r, dim = origins.shape
    b, _ = box_lo.shape
    d_pad = _round_up(dim, 8)
    br_eff = min(br, _round_up(r, 8))
    bb_eff = min(bb, _round_up(b, 8))
    o = _pad_cols(_pad_rows(origins, _round_up(r, br_eff)), d_pad)
    dv = _pad_cols(_pad_rows(directions, _round_up(r, br_eff), fill=1.0),
                   d_pad, fill=1.0)
    bl = _pad_cols(_pad_rows(box_lo, _round_up(b, bb_eff)), d_pad)
    bh = _pad_cols(_pad_rows(box_hi, _round_up(b, bb_eff)), d_pad)
    t, i = ray_box_nearest_pallas(o, dv, bl, bh, dim=dim, b_actual=b,
                                  br=br_eff, bb=bb_eff,
                                  interpret=_interpret())
    return t[:r], i[:r]


@partial(jax.jit, static_argnames=("causal", "window", "bq", "bk"))
def flash_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                    bq: int = 128, bk: int = 128):
    """Flash attention: q (B, Hq, Sq, D), k/v (B, Hkv, Skv, D) ->
    (B, Hq, Sq, D). GQA via Hq = G * Hkv; optional sliding window."""
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    bq_eff = min(bq, _round_up(sq, 8))
    bk_eff = min(bk, _round_up(skv, 8))
    sq_pad = _round_up(sq, bq_eff)
    skv_pad = _round_up(skv, bk_eff)

    def pad_seq(x, s_to):
        s = x.shape[2]
        if s == s_to:
            return x
        pad = jnp.zeros(x.shape[:2] + (s_to - s,) + x.shape[3:], x.dtype)
        return jnp.concatenate([x, pad], 2)

    qq = pad_seq(q, sq_pad)
    kk = pad_seq(k, skv_pad)
    vv = pad_seq(v, skv_pad)
    # kernel computes positions against the TRUE lengths; padded q rows
    # are garbage and sliced off, padded kv is masked via skv_actual
    out = flash_attention_pallas(qq, kk, vv, causal=causal, window=window,
                                 skv_actual=skv, sq_actual=sq,
                                 bq=bq_eff, bk=bk_eff,
                                 interpret=_interpret())
    return out[:, :, :sq]

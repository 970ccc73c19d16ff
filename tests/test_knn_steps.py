"""The kNN loop's step count and the pipeline's counters built on it.

The engine's kNN loop route counts, per query, the nodes it read: its
seed descent's records, then one a step of its lane of the vmapped
``while_loop`` (``traverse_knn(count_steps=True)``), and flags whether
its pruning bound was seeded. Both come back with the answers, and
``PipelineStats`` sums them into ``knn_rows`` / ``knn_steps`` /
``knn_lane_steps`` / ``knn_seeded_rows``. These tests pin that the
answers are exact and unchanged by the count, that the count is the
seeded rope-order walk's own, and that every other kNN path keeps its
answers.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import make_mesh
from repro.core import geometry as G
from repro.core import predicates as P
from repro.core import traversal as T
from repro.core.bvh import BVH
from repro.core.engine import EngineConfig, QueryEngine
from repro.service import (IndexStore, PipelineConfig, ServiceConfig,
                           ServingPipeline, ShardedIndexStore, knn_request)
from repro.telemetry import read_metrics_jsonl, write_metrics_jsonl

N, Q, K, DIM = 1 << 12, 256, 10, 3
SHORT = 100             # rows of a request that a bucket of 128 pads


def _pts(n, seed):
    return np.random.default_rng(seed).uniform(
        0, 1, (n, DIM)).astype(np.float32)


def _engine():
    return QueryEngine(EngineConfig(force="loop"))


def _config():
    return PipelineConfig(service=ServiceConfig(min_bucket=8, max_bucket=Q))


@pytest.fixture(scope="module")
def served():
    """Two requests served through the pipeline's loop route: Q rows (a
    full bucket) and SHORT rows (padded to 128), with the counters before
    and after each."""
    pts, qs = _pts(N, 1), _pts(Q, 2)
    pipe = ServingPipeline(store=IndexStore(_engine()), config=_config())
    try:
        ver = pipe.create_index("default", G.Points(jnp.asarray(pts)))
        stats = [pipe.stats()]
        full = pipe.submit(knn_request(qs, k=K)).result(600)
        stats.append(pipe.stats())
        short = pipe.submit(knn_request(qs[:SHORT], k=K)).result(600)
        stats.append(pipe.stats())
        export = pipe.metrics_registry
    finally:
        pipe.close()
    return dict(pts=pts, qs=qs, tree=ver.bvh.tree, values=ver.bvh.values,
                full=full, short=short, stats=stats, registry=export)


def test_loop_route_answers_equal_float64_brute_force(served):
    resp = served["full"]
    assert resp.stats.route == "loop"
    pts = served["pts"].astype(np.float64)
    qs = served["qs"].astype(np.float64)
    d64 = np.sqrt(((qs[:, None, :] - pts[None, :, :]) ** 2).sum(-1))
    ref = np.sort(d64, axis=1)[:, :K]
    assert np.abs(resp.dists - ref).max() <= 1e-6
    # each served index is at the distance served for it
    own = np.take_along_axis(d64, resp.idxs.astype(np.int64), 1)
    assert np.abs(own - resp.dists).max() <= 1e-6
    assert (resp.idxs >= 0).all()


def test_answers_bit_identical_to_the_loop_without_the_count(served):
    preds = P.nearest(G.Points(jnp.asarray(served["qs"])), k=K)
    d, i = T.traverse_knn(served["tree"], served["values"], preds, K)
    assert np.array_equal(np.asarray(d).view(np.int32),
                          served["full"].dists.view(np.int32))
    assert np.array_equal(np.asarray(i), served["full"].idxs)
    d2, i2, steps, _ = T.traverse_knn(served["tree"], served["values"],
                                      preds, K, count_steps=True)
    assert np.array_equal(np.asarray(d2).view(np.int32),
                          np.asarray(d).view(np.int32))
    assert np.array_equal(np.asarray(i2), np.asarray(i))
    assert np.array_equal(np.asarray(steps), served["full"].steps)


def _seed(tree, pts, q):
    """The seed in NumPy: one path into the nearer child (ties left), then
    the k-th smallest distance of the sorted leaves around its end, widened
    as the loop widens it. Returns (node records read, bound)."""
    lo, hi = np.asarray(tree.node_lo), np.asarray(tree.node_hi)
    rope, left = np.asarray(tree.rope), np.asarray(tree.left_child)
    perm = np.asarray(tree.leaf_perm)
    n = perm.shape[0]

    def mind(node):
        gap = np.maximum(np.maximum(lo[node] - q, q - hi[node]),
                         np.float32(0))
        return np.sqrt(np.sum(gap * gap))

    node, reads = 0, 1
    while node < n - 1:
        near, far = left[node], rope[left[node]]
        node = far if mind(far) < mind(near) else near
        reads += 2
    w = T.seed_window(K, n)
    start = min(max(node - (n - 1) - w // 2, 0), n - w)
    d = np.sort(np.sqrt(np.sum((q - pts[perm[start:start + w]]) ** 2, -1)))
    fi = np.finfo(np.float32)
    scale = (np.maximum(np.abs(lo[0]), np.abs(hi[0])).max()
             + np.abs(q).max())
    return reads, (d[K - 1] * np.float32(1 + 2 ** -10)
                   + np.float32(16 * q.shape[-1] * fi.eps) * scale + fi.tiny)


def _rewalk(tree, pts, q):
    """The kNN walk in NumPy: the seed's reads, then one step a node in
    rope order, pruned by the smaller of the seed and the k-th candidate
    distance `tau` as the loop prunes. Returns (steps, dists)."""
    lo, hi = np.asarray(tree.node_lo), np.asarray(tree.node_hi)
    rope, left = np.asarray(tree.rope), np.asarray(tree.left_child)
    perm = np.asarray(tree.leaf_perm)
    n = perm.shape[0]
    dists = np.full(K, np.inf, np.float32)
    steps, seed = _seed(tree, pts, q)
    node = 0
    while node != -1:
        steps += 1
        tau = dists[K - 1]
        gap = np.maximum(np.maximum(lo[node] - q, q - hi[node]),
                         np.float32(0))
        promising = np.sqrt(np.sum(gap * gap)) < min(tau, seed)
        if node >= n - 1:
            d = np.sqrt(np.sum((q - pts[perm[node - (n - 1)]]) ** 2))
            if promising and d < tau:
                dists = np.sort(np.append(dists, d))[:K]
            node = rope[node]
        else:
            node = left[node] if promising else rope[node]
    return steps, dists


def test_step_count_is_the_rope_order_walk(served):
    steps = served["full"].steps
    assert steps.dtype == np.int32 and steps.shape == (Q,)
    for row in np.linspace(0, Q - 1, 8).astype(int):
        walked, dists = _rewalk(served["tree"], served["pts"],
                                served["qs"][row])
        assert walked == steps[row], row
        np.testing.assert_allclose(dists, served["full"].dists[row],
                                   rtol=1e-6)


def test_pipeline_counters_advance_by_rows_steps_and_lane_steps(served):
    s0, s1, s2 = served["stats"]
    full, short = served["full"].steps, served["short"].steps
    assert short.shape == (SHORT,)
    assert np.array_equal(short, full[:SHORT])
    assert s1.knn_rows - s0.knn_rows == Q
    assert s1.knn_steps - s0.knn_steps == int(full.sum())
    assert s1.knn_lane_steps - s0.knn_lane_steps == int(full.max()) * Q
    # the short request rides a bucket of 128: its padding repeats the
    # last real row, so the largest real count is the loop's trip count
    assert served["short"].stats.bucket == 128
    assert s2.knn_rows - s1.knn_rows == SHORT
    assert s2.knn_steps - s1.knn_steps == int(short.sum())
    assert s2.knn_lane_steps - s1.knn_lane_steps == int(short.max()) * 128
    assert 0 < s2.knn_steps <= s2.knn_lane_steps


def test_seeded_rows_sum_like_rows(served):
    """Every row of the uniform cloud starts from a seeded bound, so
    ``knn_seeded_rows`` advances with ``knn_rows``: the real rows' flags,
    not the padding's."""
    s0, s1, s2 = served["stats"]
    full, short = served["full"].seeded, served["short"].seeded
    assert full.dtype == np.bool_ and full.shape == (Q,)
    assert short.shape == (SHORT,) and full.all() and short.all()
    assert s1.knn_seeded_rows - s0.knn_seeded_rows == int(full.sum()) == Q
    assert s2.knn_seeded_rows - s1.knn_seeded_rows == int(short.sum())
    assert s2.knn_seeded_rows == s2.knn_rows


def test_counters_appear_in_the_metrics_export(served, tmp_path):
    path = str(tmp_path / "pipeline.jsonl")
    assert write_metrics_jsonl(path, served["registry"]) > 0
    back = read_metrics_jsonl(path)
    last = served["stats"][-1]
    for name in ("knn_rows", "knn_steps", "knn_lane_steps",
                 "knn_seeded_rows"):
        rec = back[f"pipeline.{name}"]
        assert rec["type"] == "counter"
        assert rec["value"] == getattr(last, name) > 0


def test_brute_force_route_counts_nothing():
    """Only the loop route counts: a batch the engine serves by brute force
    leaves the counters alone and its responses without steps."""
    pipe = ServingPipeline(
        store=IndexStore(QueryEngine(EngineConfig(force="bruteforce"))),
        config=_config())
    try:
        pipe.create_index("default", G.Points(jnp.asarray(_pts(64, 3))))
        resp = pipe.submit(knn_request(_pts(8, 4), k=2)).result(600)
        st = pipe.stats()
    finally:
        pipe.close()
    assert resp.stats.route == "bruteforce" and resp.steps is None
    assert (st.knn_rows, st.knn_steps, st.knn_lane_steps,
            st.knn_seeded_rows) == (0, 0, 0, 0)


@pytest.mark.parametrize("path", ["sharded_local_stage", "bvh_query"])
def test_other_knn_paths_keep_their_answers(served, path):
    """The sharded local stage and ``BVH.query`` run the loop without the
    count; their answers are those of the pipeline's loop route."""
    qs = served["qs"]
    if path == "sharded_local_stage":
        pipe = ServingPipeline(store=ShardedIndexStore(
            make_mesh((1,), ("data",)), "data"), config=_config())
        try:
            pipe.create_index("default", served["pts"])
            resp = pipe.submit(knn_request(qs, k=K)).result(600)
        finally:
            pipe.close()
        assert resp.stats.route == "sharded" and resp.steps is None
        d, i = resp.dists, resp.idxs
    else:
        bvh = BVH(G.Points(jnp.asarray(served["pts"])), engine=_engine())
        res = bvh.query(P.nearest(G.Points(jnp.asarray(qs)), k=K))
        d, i = np.asarray(res.distances), np.asarray(res.indices)
    assert np.array_equal(d.view(np.int32),
                          served["full"].dists.view(np.int32))
    assert np.array_equal(i, served["full"].idxs)

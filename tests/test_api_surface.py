"""API-surface CI (ISSUE 5 satellite): the three indexes expose literally
identical ``query``/``count`` signatures, ``repro.core.__all__`` stays in
sync with the actual exports, and the legacy API-v1 spellings are
deprecation shims (the tier-1 runner executes under
``-W error::DeprecationWarning`` so stray in-repo legacy call sites fail
loudly)."""
import inspect

import jax.numpy as jnp
import numpy as np
import pytest

import repro.core as core
from repro.core import geometry as G, predicates as P
from repro.core.brute_force import BruteForce
from repro.core.bvh import BVH
from repro.core.distributed import DistributedTree
from repro.core.index import ExecutionPolicy, Index
from repro.core import index as IX


def test_query_signature_identical_across_indexes():
    sig = inspect.signature(Index.query)
    for cls in (BVH, BruteForce, DistributedTree):
        assert inspect.signature(cls.query) == sig, cls
    # the unified signature is the ISSUE-5 contract
    assert [p for p in sig.parameters] == \
        ["self", "predicates", "_legacy", "callback", "out", "capacity",
         "policy"]


def test_count_signature_identical_across_indexes():
    sig = inspect.signature(Index.count)
    for cls in (BVH, BruteForce, DistributedTree):
        assert inspect.signature(cls.count) == sig, cls


def test_constructor_contract():
    """Construction is (values, indexable_getter=..., policy=...) on every
    backend (DistributedTree prepends its mesh/axis pair)."""
    for cls, skip in ((BVH, 0), (BruteForce, 0), (DistributedTree, 2)):
        params = list(inspect.signature(cls.__init__).parameters)[1 + skip:]
        assert params[0] == "values", cls
        assert params[1] == "indexable_getter", cls
        assert "policy" in params, cls


def test_core_all_matches_exports():
    names = set(core.__all__)
    assert len(core.__all__) == len(names), "duplicates in __all__"
    for name in names:
        assert hasattr(core, name), f"__all__ lists missing export {name}"
    # every public class/function living under repro.core must be listed
    for name in dir(core):
        if name.startswith("_"):
            continue
        obj = getattr(core, name)
        if inspect.ismodule(obj):
            continue
        if getattr(obj, "__module__", "").startswith("repro.core"):
            assert name in names, f"public export {name} missing in __all__"
    for required in ("Index", "ExecutionPolicy", "QueryResult", "BVH",
                     "BruteForce", "DistributedTree"):
        assert required in names


def _mk():
    r = np.random.default_rng(0)
    vals = G.Points(jnp.asarray(r.uniform(0, 1, (50, 3)).astype(np.float32)))
    q = jnp.asarray(r.uniform(0, 1, (4, 3)).astype(np.float32))
    return vals, P.intersects(G.Spheres(q, jnp.full((4,), 0.3))), q


def test_legacy_spellings_warn_deprecation():
    vals, preds, q = _mk()
    knn = P.nearest(G.Points(q), k=2)

    IX._SEEN_DEPRECATIONS.clear()
    with pytest.warns(DeprecationWarning, match="deprecated"):
        bvh = BVH(None, vals)                     # space-first constructor
    IX._SEEN_DEPRECATIONS.clear()
    with pytest.warns(DeprecationWarning, match="QueryResult"):
        v, i, o = bvh.query(None, preds)          # legacy triple unpack
    IX._SEEN_DEPRECATIONS.clear()
    with pytest.warns(DeprecationWarning):
        c = bvh.count(None, preds)
    assert np.array_equal(np.asarray(np.diff(np.asarray(o))), np.asarray(c))
    IX._SEEN_DEPRECATIONS.clear()
    with pytest.warns(DeprecationWarning):
        d, idx = bvh.knn(None, knn)
    assert d.shape == (4, 2)
    IX._SEEN_DEPRECATIONS.clear()
    with pytest.warns(DeprecationWarning):
        out, off = bvh.query_out(None, preds, lambda p, v, i, t: t)
    IX._SEEN_DEPRECATIONS.clear()


def test_legacy_warnings_fire_once_per_spelling():
    import warnings
    vals, preds, _ = _mk()
    bvh = BVH(vals)
    IX._SEEN_DEPRECATIONS.clear()
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        bvh.count(None, preds)
        bvh.count(None, preds)
    assert len([w for w in rec
                if issubclass(w.category, DeprecationWarning)]) == 1
    IX._SEEN_DEPRECATIONS.clear()


def test_engine_config_has_two_fields():
    """The engine's only knobs are a forced route and the executable-cache
    bound: the crossover is one constant (``engine.BF_MAX_WORK``), and
    neither the config nor an ExecutionPolicy carries a route table."""
    import dataclasses

    from repro.core.engine import EngineConfig

    assert [f.name for f in dataclasses.fields(EngineConfig)] == \
        ["force", "max_executables"]
    assert "route_table" not in {
        f.name for f in dataclasses.fields(ExecutionPolicy)}
    with pytest.raises(TypeError):
        EngineConfig(brute_force_max_work=123)


def test_new_api_is_warning_free():
    import warnings
    vals, preds, q = _mk()
    IX._SEEN_DEPRECATIONS.clear()
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        bvh = BVH(vals)
        bvh.query(preds)
        bvh.count(preds)
        bvh.query(P.nearest(G.Points(q), k=2))
        BruteForce(vals).query(preds)


def test_stats_legacy_kwargs_warn_and_seed_the_registry():
    """ISSUE 9: EngineStats/PipelineStats fields moved into a telemetry
    MetricsRegistry; constructing with field keyword arguments still works
    but warns once (the values now live in stats.registry)."""
    from repro.core.engine import EngineStats
    from repro.service.pipeline import PipelineStats

    IX._SEEN_DEPRECATIONS.clear()
    with pytest.warns(DeprecationWarning, match="MetricsRegistry"):
        es = EngineStats(cache_hits=5, jit_traces=2)
    assert (es.cache_hits, es.cache_misses, es.jit_traces) == (5, 0, 2)
    es.cache_hits += 1                        # legacy spelling still lands
    assert es.registry.snapshot()["engine.cache_hits"]["value"] == 6
    with pytest.raises(TypeError, match="unexpected"):
        EngineStats(nope=1)

    IX._SEEN_DEPRECATIONS.clear()
    with pytest.warns(DeprecationWarning, match="MetricsRegistry"):
        ps = PipelineStats(submitted=3, max_queue_depth=7)
    assert ps.submitted == 3 and ps.max_queue_depth == 7
    assert ps.registry.snapshot()["pipeline.queue_depth"]["high"] == 7
    with pytest.raises(TypeError, match="unexpected"):
        PipelineStats(nope=1)
    IX._SEEN_DEPRECATIONS.clear()


def test_max_queue_depth_setter_is_a_warn_once_extend_only_shim():
    """The high-water mark updates atomically inside every queue_depth
    change now; direct assignment warns and can only EXTEND the mark
    (the racy read-modify-write spelling could silently lower it)."""
    import warnings
    from repro.service.pipeline import PipelineStats

    ps = PipelineStats()
    ps.queue_depth += 5
    assert ps.max_queue_depth == 5            # tracked by the gauge itself
    IX._SEEN_DEPRECATIONS.clear()
    with pytest.warns(DeprecationWarning, match="note_high"):
        ps.max_queue_depth = 2                # lower: ignored
    assert ps.max_queue_depth == 5
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        ps.max_queue_depth = 9                # higher: extends, no re-warn
    assert ps.max_queue_depth == 9
    assert not [w for w in rec if issubclass(w.category, DeprecationWarning)]
    IX._SEEN_DEPRECATIONS.clear()


def test_stats_warn_once_per_spelling():
    import warnings
    from repro.core.engine import EngineStats
    IX._SEEN_DEPRECATIONS.clear()
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        EngineStats(cache_hits=1)
        EngineStats(cache_hits=2)
    assert len([w for w in rec
                if issubclass(w.category, DeprecationWarning)]) == 1
    IX._SEEN_DEPRECATIONS.clear()


def test_new_stats_spellings_are_warning_free():
    import warnings
    from repro.core.engine import EngineStats
    from repro.service.pipeline import PipelineStats
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        es = EngineStats()
        es.cache_hits += 1
        assert es.snapshot().cache_hits == 1
        ps = PipelineStats()
        ps.queue_depth += 1
        assert ps.max_queue_depth == 1        # reading the mark is free
        assert ps.snapshot().queue_depth == 1


def test_ship_values_baseline_is_a_warn_once_shim():
    """ISSUE 10: the fixed-capacity value-shipping helper is deprecated in
    favor of the policy-gated opt-in (``override(ship_values=True)``),
    which ships exactly the matched set through the unified query()."""
    import warnings

    from repro.compat import make_mesh
    from repro.core.distributed import DistributedTree, ship_values_baseline

    pts = np.random.default_rng(0).uniform(0, 1, (16, 3)).astype(np.float32)
    tree = DistributedTree(make_mesh((1,), ("data",)), "data", pts)
    q = jnp.asarray(pts[:4])

    IX._SEEN_DEPRECATIONS.clear()
    with pytest.warns(DeprecationWarning, match="ship_values=True"):
        ship_values_baseline(tree, q, 0.3, 4)
    with warnings.catch_warnings(record=True) as rec:   # warn-once
        warnings.simplefilter("always")
        ship_values_baseline(tree, q, 0.3, 4)
    assert not [w for w in rec if issubclass(w.category, DeprecationWarning)]
    IX._SEEN_DEPRECATIONS.clear()

    # the new spelling is warning-free and populates QueryResult.values
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        res = tree.query(
            P.intersects(G.Spheres(q, jnp.full((4,), 0.3, jnp.float32))),
            policy=tree.policy.override(ship_values=True))
    assert res.values is not None

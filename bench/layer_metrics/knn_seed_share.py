"""Share of the kNN loop's real rows whose pruning bound was seeded (%),
over the traced window: the change of ``PipelineStats.knn_seeded_rows``
over that of ``knn_rows``. A seeded row starts its walk bounded by the
k-th distance of the sorted leaves next to a one-path descent. None where
the pipeline has no such counters or served no kNN row by the loop."""


def _delta(run, name):
    a = getattr(run.stats0, name, None)
    b = getattr(run.stats1, name, None)
    return None if a is None or b is None else b - a


def read(run):
    rows, seeded = _delta(run, "knn_rows"), _delta(run, "knn_seeded_rows")
    if not rows or seeded is None:
        return None
    return seeded / rows * 100.0

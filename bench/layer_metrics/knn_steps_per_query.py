"""Nodes the kNN loop visited per query row, over the traced window: the
change of ``PipelineStats.knn_steps`` over that of ``knn_rows``. None where
the pipeline has no such counters or served no kNN row by the loop."""


def _delta(run, name):
    a = getattr(run.stats0, name, None)
    b = getattr(run.stats1, name, None)
    return None if a is None or b is None else b - a


def read(run):
    rows, steps = _delta(run, "knn_rows"), _delta(run, "knn_steps")
    if not rows or steps is None:
        return None
    return steps / rows

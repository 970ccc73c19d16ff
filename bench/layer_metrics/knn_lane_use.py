"""Share of the kNN loop's lane-steps that did work (%), over the traced
window: the change of ``PipelineStats.knn_steps`` over that of
``knn_lane_steps`` (each batch's trip count times its bucket slots). A
lane whose query is done idles until the batch's slowest lane is. None
where the pipeline has no such counters or ran no kNN loop."""


def _delta(run, name):
    a = getattr(run.stats0, name, None)
    b = getattr(run.stats1, name, None)
    return None if a is None or b is None else b - a


def read(run):
    lanes, steps = _delta(run, "knn_lane_steps"), _delta(run, "knn_steps")
    if not lanes or steps is None:
        return None
    return steps / lanes * 100.0

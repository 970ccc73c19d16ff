"""Device milliseconds per run of the sharded local-traversal stage
(``jit_local_fn``, each shard answering every query of the batch against
its own tree); the mean per run, on the slowest device."""


def read(run):
    tr = run.trace
    if tr is None:
        return None
    per = []
    for d in tr.device_names():
        evs = tr.modules(d, r"^jit_local_fn")
        if evs:
            per.append(sum(e.dur_ns for e in evs) / len(evs) * 1e-6)
    return max(per) if per else None

"""Device milliseconds of index maintenance per update: the store's refit
(``jit_refit``) and quality (``jit_sah_cost``) programs in the trace, over
the refits it holds."""


def read(run):
    tr = run.trace
    if tr is None:
        return None
    per = []
    for d in tr.device_names():
        refits = len(tr.modules(d, r"^jit_refit"))
        if refits:
            ns = sum(e.dur_ns for e in tr.modules(d, r"^jit_(refit|sah_cost)"))
            per.append(ns * 1e-6 / refits)
    return max(per) if per else None

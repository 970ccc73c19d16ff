"""Host milliseconds per batch in the scheduler's ``pipeline.deliver``
span (answers scattered to the requests, their futures completed, the
batch accounted), the mean over the batches of the traced window. The
span is a host event of the profiler's trace; a program without it
reports nothing."""


def read(run):
    tr = run.trace
    durs = [e.dur_ns for _, e in (tr.host if tr is not None else ())
            if e.name == "pipeline.deliver"]
    return sum(durs) / len(durs) * 1e-6 if durs else None

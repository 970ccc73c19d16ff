"""95th percentile of the pipeline's queue wait (ms): submit to batch
dispatch, as ``RequestStats.queue_wait_us`` gives it, over every answered
request of the window."""
from bench.measure import percentile


def read(run):
    waits = [r.resp.stats.queue_wait_us * 1e-3 for r in run.window.sent
             if r.answered]
    return percentile(waits, 95) if waits else None

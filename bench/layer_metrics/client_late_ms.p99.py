"""99th percentile of how late the benchmark's client submitted requests
against their due times (ms): a starved client, not a slow server."""
from bench.measure import late_ms, percentile


def read(run):
    late = late_ms(run.window)
    return percentile(late, 99) if late else None

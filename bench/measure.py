"""End-to-end numbers from a window's host-clock record (``drive.Window``).

* ``qps`` -- every query row of the requests sent in the window, over the
  time from the first submission to the answer of the last of them. The
  window thus ends on an answer: a batch half done at the close is neither
  cut nor left out, and a stall anywhere in the window counts.
* ``p95_ms`` -- the 95th percentile (nearest rank) of the time from each
  request's due time to its answer, over every request due in the window.
  A request with no answer counts as waiting until the clients gave up.
* ``publish_ms`` -- the mean time from ``update_index`` to the moved
  cloud's version being the one served, over the updates of the window.
"""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q% of the
    values at or below it."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    return xs[max(0, math.ceil(q / 100.0 * len(xs)) - 1)]


def qps(window) -> float:
    rows = sum(r.rows for r in window.sent if r.answered)
    return rows / (window.t_end - window.t0)


def latencies_ms(window, gave_up: float) -> list[float]:
    """Due time to answer of every request; `gave_up` for those without."""
    return [((r.t_done if r.answered else gave_up) - r.t_due) * 1e3
            for r in window.sent]


def p95_ms(window, gave_up: float) -> float:
    return percentile(latencies_ms(window, gave_up), 95)


def publish_ms(window) -> float | None:
    done = [(m.t_visible - m.t_call) * 1e3 for m in window.moved
            if m.error is None and not math.isnan(m.t_visible)]
    return sum(done) / len(done) if done else None


def late_ms(window) -> list[float]:
    """How late each submission ran against its due time."""
    return [(r.t_sub - r.t_due) * 1e3 for r in window.sent
            if not math.isnan(r.t_sub)]

"""The end-to-end arithmetic on synthetic host-clock records."""
import math

import numpy as np
import pytest

from bench import measure
from bench.drive import Moved, Sent, Window


def _sent(t_due, t_sub, t_done, rows=1024, answered=True):
    r = Sent(0, np.zeros((rows, 3), np.float32), t_due, t_sub, t_done)
    r.resp = object() if answered else None
    return r


def test_qps_counts_every_row_to_the_last_answer():
    # two clients, 1 s batches served one after the other; the window
    # closes at 3.5 s while the fifth batch is queued, which ends at 5 s
    sent = [_sent(0, 0, 1), _sent(0, 0, 2), _sent(1, 1, 3), _sent(2, 2, 4),
            _sent(3, 3, 5)]
    w = Window(t0=0.0, t_close=3.5, t_end=5.0, sent=sent, moved=[])
    assert measure.qps(w) == pytest.approx(5 * 1024 / 5.0)


def test_a_stall_at_the_end_of_the_window_counts():
    sent = [_sent(0, 0, 1), _sent(0, 0, 2), _sent(1, 1, 3), _sent(2, 2, 4),
            _sent(3, 3, 9)]                     # the last batch stalls 4 s
    w = Window(t0=0.0, t_close=3.5, t_end=9.0, sent=sent, moved=[])
    assert measure.qps(w) == pytest.approx(5 * 1024 / 9.0)


def test_p95_is_from_the_due_time_and_counts_the_missing():
    # 100 requests due every 10 ms, each answered 5 ms after its due time,
    # except that the generator ran 20 ms late for the last 10
    sent = []
    for i in range(100):
        due = i * 0.01
        late = 0.02 if i >= 90 else 0.0
        sent.append(_sent(due, due + late, due + late + 0.005, rows=1))
    w = Window(0.0, 1.0, max(r.t_done for r in sent), sent, [])
    lat = measure.latencies_ms(w, gave_up=61.0)
    assert sorted(lat)[0] == pytest.approx(5.0)
    assert measure.p95_ms(w, gave_up=61.0) == pytest.approx(25.0)
    assert measure.percentile(measure.late_ms(w), 99) == pytest.approx(20.0)
    # five requests never answered wait until the clients give up: the
    # 95th percentile is the slowest answer; with six it is a wait
    for r in sent[:5]:
        r.resp, r.t_done = None, math.nan
    assert measure.p95_ms(w, gave_up=61.0) == pytest.approx(25.0)
    sent[5].resp, sent[5].t_done = None, math.nan
    assert measure.p95_ms(w, gave_up=61.0) > 60_000.0 - 100.0


def test_percentile_is_nearest_rank():
    assert measure.percentile(range(1, 101), 95) == 95
    assert measure.percentile([3.0], 95) == 3.0
    assert measure.percentile([1, 2], 50) == 1


def test_publish_ms_is_the_mean_over_visible_updates():
    moved = [Moved(0, 1.0, 1.0, 1.25, 3, "refit"),
             Moved(1, 6.0, 6.0, 6.35, 4, "refit"),
             Moved(2, 11.0, 11.0, math.nan, 0, "", error="boom")]
    w = Window(0.0, 15.0, 15.0, [], moved)
    assert measure.publish_ms(w) == pytest.approx(300.0)

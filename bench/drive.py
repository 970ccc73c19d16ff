"""The clients: a closed loop and an open loop, timed on the host clock.

Every request keeps its queries, the times it was due, submitted and
answered (``time.perf_counter``), and the answer or the error. A request
that has no answer a minute after the window closes is left without one
and counts as missing.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time

import numpy as np

#: how long after the window closes the clients wait for answers
GRACE_S = 60.0


@dataclasses.dataclass
class Sent:
    client: int
    a: np.ndarray
    t_due: float
    t_sub: float = float("nan")
    t_done: float = float("nan")
    resp: object = None
    error: str | None = None

    @property
    def rows(self) -> int:
        return len(self.a)

    @property
    def answered(self) -> bool:
        return self.resp is not None


@dataclasses.dataclass
class Moved:
    index: int            # which moved copy of the cloud
    t_due: float
    t_call: float = float("nan")
    t_visible: float = float("nan")
    version: int = 0
    action: str = ""
    error: str | None = None


@dataclasses.dataclass
class Window:
    t0: float             # the window opens (first submission)
    t_close: float        # t0 + seconds: no request is sent after this
    t_end: float          # the last answer of a request sent in the window
    sent: list
    moved: list


def _wait(ticket, rec: Sent, deadline: float):
    try:
        rec.resp = ticket.result(max(deadline - time.perf_counter(), 0.0))
        rec.t_done = time.perf_counter()
    except TimeoutError:
        rec.error = "no answer within the grace period"
    except Exception as err:                     # the request failed
        rec.t_done = time.perf_counter()
        rec.error = f"{type(err).__name__}: {err}"


def closed_loop(system, traffic, seconds: float) -> Window:
    """`clients` clients, each sending its next request of `rows` rows as
    soon as the previous one is answered, until `seconds` have passed."""
    clients = int(traffic.mix["clients"])
    rows = int(traffic.mix["rows"])
    sent: list[list[Sent]] = [[] for _ in range(clients)]
    start = threading.Barrier(clients + 1)
    t0_box = []

    def client(c: int):
        rng = traffic.rng(3, c)
        start.wait()
        t0 = t0_box[0]
        while time.perf_counter() - t0 < seconds:
            a = traffic.queries(rng, rows)
            req = system.request(a)
            rec = Sent(c, a, t_due=time.perf_counter())
            rec.t_sub = rec.t_due
            sent[c].append(rec)
            ticket = system.submit(req)
            _wait(ticket, rec, t0 + seconds + GRACE_S)
            if rec.resp is None:
                return

    threads = [threading.Thread(target=client, args=(c,), daemon=True,
                                name=f"bench-client-{c}")
               for c in range(clients)]
    for th in threads:
        th.start()
    t0_box.append(time.perf_counter())
    start.wait()
    for th in threads:
        th.join(seconds + GRACE_S + 5.0)
    flat = sorted((r for rs in sent for r in rs), key=lambda r: r.t_sub)
    done = [r.t_done for r in flat if r.answered]
    return Window(t0_box[0], t0_box[0] + seconds,
                  max(done) if done else t0_box[0] + seconds, flat, [])


def open_loop(system, traffic, seconds: float, moved_points: list) -> Window:
    """Requests sent when they are due, whatever the system's state, and
    the moved clouds handed to ``update_index`` at their times.
    `moved_points` are the placed moved clouds, one per update time."""
    due = traffic.schedule(seconds)
    rng = traffic.rng(3, 0)
    sent = [Sent(0, traffic.queries(rng, d.rows), d.t) for d in due]
    reqs = [system.request(s.a) for s in sent]
    moved = [Moved(i, t) for i, t in enumerate(traffic.update_times(seconds))]
    tickets: queue.Queue = queue.Queue()
    t0 = time.perf_counter() + 0.05
    deadline = t0 + seconds + GRACE_S

    def submitter():
        for rec, req in zip(sent, reqs):
            rec.t_due += t0
            pause = rec.t_due - time.perf_counter()
            if pause > 0:
                time.sleep(pause)
            rec.t_sub = time.perf_counter()
            tickets.put((rec, system.submit(req)))
        tickets.put(None)

    def waiter():
        while (item := tickets.get()) is not None:
            _wait(item[1], item[0], deadline)

    def updater():
        for m in moved:
            m.t_due += t0
            pause = m.t_due - time.perf_counter()
            if pause > 0:
                time.sleep(pause)
            m.t_call = time.perf_counter()
            try:
                m.version, m.action = system.update(
                    moved_points[m.index], max(deadline - m.t_call, 0.0))
                m.t_visible = time.perf_counter()
            except Exception as err:
                m.error = f"{type(err).__name__}: {err}"

    threads = [threading.Thread(target=f, daemon=True, name=f"bench-{n}")
               for n, f in (("submitter", submitter), ("waiter", waiter),
                            ("updater", updater))]
    for th in threads:
        th.start()
    for th in threads:
        th.join(seconds + GRACE_S + 5.0)
    done = [r.t_done for r in sent if r.answered]
    return Window(t0, t0 + seconds, max(done) if done else t0 + seconds,
                  sent, moved)

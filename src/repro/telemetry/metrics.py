"""Metrics registry: counters and gauges (DESIGN.md §10).

The registry replaces the repo's ad-hoc stats islands (``EngineStats``,
``PipelineStats``) with one vocabulary:

  * :class:`Counter`  — monotone-by-convention total (``add``);
  * :class:`Gauge`    — instantaneous level with an atomically-tracked
    high-water mark (``adjust``/``set`` update value AND high under the
    registry lock, so a concurrent reader can never observe a level
    above the recorded high — the queue-depth bug class).

All mutation goes through one registry-level lock: metric updates are a
few nanoseconds of bookkeeping, never device syncs, so a shared lock is
cheaper than per-metric locks and keeps ``snapshot()`` a consistent cut
across every metric at once.
"""
from __future__ import annotations

import threading

__all__ = ["Counter", "Gauge", "MetricsRegistry"]


class Counter:
    __slots__ = ("name", "_lock", "_value")

    def __init__(self, name: str, lock: threading.Lock):
        self.name = name
        self._lock = lock
        self._value = 0

    def add(self, n=1):
        with self._lock:
            self._value += n

    @property
    def value(self):
        return self._value

    def set(self, v):
        """Direct (re)set — the compatibility-property write path for the
        legacy ``stats.field = x`` / ``stats.field += 1`` spellings (the
        += read-modify-write is exactly as race-prone as it was on the
        old dataclasses; the serving code always holds its stats lock
        around it, and new code should call ``add``)."""
        with self._lock:
            self._value = v

    def to_dict(self) -> dict:
        return {"type": "counter", "value": self._value}


class Gauge:
    __slots__ = ("name", "_lock", "_value", "_high")

    def __init__(self, name: str, lock: threading.Lock):
        self.name = name
        self._lock = lock
        self._value = 0
        self._high = 0

    def adjust(self, delta):
        """Atomic level change; the high-water mark updates under the
        same lock, so it can never under-report a peak two threads built
        together."""
        with self._lock:
            self._value += delta
            if self._value > self._high:
                self._high = self._value
            return self._value

    def set(self, v):
        with self._lock:
            self._value = v
            if v > self._high:
                self._high = v

    def note_high(self, v):
        """Seed/extend the high-water mark without touching the level."""
        with self._lock:
            if v > self._high:
                self._high = v

    @property
    def value(self):
        return self._value

    @property
    def high(self):
        return self._high

    def to_dict(self) -> dict:
        return {"type": "gauge", "value": self._value, "high": self._high}


class MetricsRegistry:
    """Named get-or-create metric registry; one lock for all mutation."""

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics: dict = {}

    def _get(self, name: str, cls):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = cls(name, self._lock)
            elif not isinstance(m, cls):
                raise TypeError(f"metric {name!r} already registered as "
                                f"{type(m).__name__}, not {cls.__name__}")
            return m

    def counter(self, name: str) -> Counter:
        return self._get(name, Counter)

    def gauge(self, name: str) -> Gauge:
        return self._get(name, Gauge)

    def names(self) -> list:
        with self._lock:
            return sorted(self._metrics)

    def snapshot(self) -> dict:
        """JSON-ready {name: metric dict} consistent cut."""
        with self._lock:
            items = list(self._metrics.items())
        return {name: m.to_dict() for name, m in sorted(items)}

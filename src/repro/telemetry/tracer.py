"""Tracing spans with explicit clocks (DESIGN.md §10).

One :class:`Tracer` per process (the module-level default) records
*spans* — named, nested, timed intervals — into a bounded ring buffer.
Every span site has two sinks:

  * **the ring** (telemetry enabled: ``REPRO_TELEMETRY=1`` or
    :func:`enable`) — spans land in a ``deque(maxlen=capacity)``, so a
    long-lived service can stay instrumented forever; old spans fall off
    the back. Times are ``time.perf_counter_ns`` (monotonic,
    thread-safe). Kernel/compile spans call :meth:`_SpanCtx.fence` on
    the result, which blocks until the device work is done and marks the
    span ``clock="device"``: its duration then includes device
    execution, not just async dispatch;
  * **the JAX profiler's trace** (whenever a profiler records,
    ``jax.profiler.TraceAnnotation.is_enabled()``) — the span is also a
    host event on the profiler's own clock, its args the event's stats,
    beside the device's events. With telemetry disabled such a span
    never fences, so a profiled program keeps XLA's async dispatch.

With both off the module-level ``span()`` helper returns a shared no-op
context manager after one flag test and one ``is_enabled()`` check (the
overhead test pins < 1% of serving wall time, and nothing telemetry
does is ever visible to jit — no recompiles either way).

Nesting is tracked per thread (a ``threading.local`` stack), so spans
opened on the scheduler thread never parent spans opened on the
maintenance thread. A loop that must be tiled by flat, non-overlapping
spans (the pipeline's scheduler) runs :func:`phases`, and code it calls
switches the open phase with :func:`phase`. Spans whose boundaries are
only known after the fact (per-request phase attribution in the
pipeline) are recorded retroactively with :meth:`Tracer.add_span`, into
the ring only: the profiler takes no past timestamps.

The reprolint TEL001 pass enforces that every manually-opened span is
closed on all exception paths; ``with span(...)`` satisfies it by
construction.
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import os
import threading
import time

from jax.profiler import TraceAnnotation

__all__ = ["Span", "Tracer", "span", "phases", "phase", "enable", "disable",
           "enabled", "get_tracer", "set_tracer"]

#: is a JAX profiler recording right now? (~0.2 µs)
_profiling = TraceAnnotation.is_enabled


@dataclasses.dataclass(frozen=True)
class Span:
    """One recorded interval. Times are ``perf_counter_ns`` values; the
    exporters convert to trace-relative microseconds."""
    name: str
    span_id: int
    parent_id: int          # 0 = root
    tid: str                # thread name
    t0_ns: int
    dur_ns: int
    clock: str = "wall"     # "wall" | "device" (fenced via block_until_ready)
    args: dict = dataclasses.field(default_factory=dict)


class _NullSpan:
    """Shared no-op context manager handed out while telemetry is
    disabled and no profiler records; every method is a pass so
    instrumented call sites need no enabled-checks of their own."""

    __slots__ = ()
    span_id = 0
    dur_us = 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def annotate(self, **kw):
        return self

    def fence(self, value):
        return value


NULL_SPAN = _NullSpan()


class _ProfiledSpan:
    """Span handed out while telemetry is disabled but a JAX profiler
    records: a host event in the profiler's trace and nothing else.
    ``fence`` returns its value without blocking and ``dur_us`` stays
    0.0, so the profiled program keeps its async dispatch."""

    __slots__ = ("_ann",)
    span_id = 0
    dur_us = 0.0

    def __init__(self, name: str, args: dict):
        self._ann = TraceAnnotation(name, **args)

    def __enter__(self):
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        return False

    def annotate(self, **kw):
        self._ann.set_metadata(**kw)
        return self

    def fence(self, value):
        return value


class _SpanCtx:
    """Live (open) span: a context manager that records itself into the
    tracer ring on exit — including exception exits, which is the close
    guarantee TEL001 checks statically."""

    __slots__ = ("_tracer", "name", "span_id", "parent_id", "args",
                 "_t0", "_t_fence", "_dur", "clock", "_ann")

    def __init__(self, tracer: "Tracer", name: str, args: dict):
        self._tracer = tracer
        self.name = name
        self.span_id = next(tracer._ids)
        self.parent_id = 0
        self.args = args
        self._t0 = 0
        self._t_fence = None
        self._dur = 0
        self.clock = "wall"
        self._ann = None

    def __enter__(self):
        stack = self._tracer._stack()
        self.parent_id = stack[-1] if stack else 0
        stack.append(self.span_id)
        if _profiling():
            self._ann = TraceAnnotation(self.name, **self.args)
            self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = self._t_fence if self._t_fence is not None \
            else time.perf_counter_ns()
        stack = self._tracer._stack()
        if stack and stack[-1] == self.span_id:
            stack.pop()
        if exc and exc[0] is not None:
            self.args = dict(self.args, error=getattr(
                exc[0], "__name__", str(exc[0])))
        if self._ann is not None:
            if "error" in self.args:
                self._ann.set_metadata(error=self.args["error"])
            self._ann.__exit__(*exc)
            self._ann = None
        self._dur = max(t1 - self._t0, 0)
        self._tracer._record(Span(
            name=self.name, span_id=self.span_id, parent_id=self.parent_id,
            tid=threading.current_thread().name, t0_ns=self._t0,
            dur_ns=self._dur, clock=self.clock, args=self.args))
        return False

    @property
    def dur_us(self) -> float:
        """Recorded duration in microseconds (0.0 until the span closes).
        Lets a caller reuse the span's own timing — e.g. the engine feeds
        it into ``ExecInfo.kernel_us`` — instead of re-measuring."""
        return self._dur / 1e3

    def annotate(self, **kw):
        self.args = dict(self.args, **kw)
        if self._ann is not None:
            self._ann.set_metadata(**kw)
        return self

    def fence(self, value):
        """Block until `value` (any pytree of jax arrays) is computed on
        device, then stamp the span as device-clocked: its duration now
        covers kernel execution, not just async dispatch. Returns
        `value` for drop-in wrapping."""
        import jax
        jax.block_until_ready(value)
        self._t_fence = time.perf_counter_ns()
        self.clock = "device"
        return value


class Tracer:
    """Thread-safe ring-buffered span recorder."""

    def __init__(self, capacity: int = 8192):
        self._ring: collections.deque = collections.deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self._tls = threading.local()
        self.epoch_ns = time.perf_counter_ns()

    # -- recording -----------------------------------------------------------
    def span(self, name: str, **args) -> _SpanCtx:
        """Open a nested span: ``with tracer.span("engine.kernel") as sp``.
        Always use ``with`` (or try/finally) — TEL001 enforces it."""
        return _SpanCtx(self, name, args)

    def add_span(self, name: str, t0_ns: int, t1_ns: int, *,
                 parent_id: int = 0, tid: str | None = None,
                 clock: str = "wall", **args) -> int:
        """Record a span whose boundaries were measured elsewhere (the
        pipeline's per-request phase attribution: the phases are only
        known once the batch completes). Returns the new span id."""
        sid = next(self._ids)
        self._record(Span(
            name=name, span_id=sid, parent_id=parent_id,
            tid=tid if tid is not None else threading.current_thread().name,
            t0_ns=t0_ns, dur_ns=max(t1_ns - t0_ns, 0), clock=clock,
            args=args))
        return sid

    def _record(self, span_: Span):
        with self._lock:
            self._ring.append(span_)

    def _stack(self) -> list:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    # -- reading -------------------------------------------------------------
    def spans(self) -> list:
        """Snapshot of the ring (oldest first), without clearing."""
        with self._lock:
            return list(self._ring)

    def drain(self) -> list:
        """Snapshot AND clear the ring."""
        with self._lock:
            out = list(self._ring)
            self._ring.clear()
        return out

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)


class _Config:
    """Module-level switch + default tracer. ``enabled`` is a plain bool
    read once per ``span()`` call; with it off, the profiler check is the
    rest of the disabled-path cost."""

    __slots__ = ("enabled", "tracer")

    def __init__(self):
        self.enabled = os.environ.get(
            "REPRO_TELEMETRY", "") not in ("", "0", "off")
        self.tracer = Tracer()


_CONFIG = _Config()


def span(name: str, **args):
    """Module-level convenience: a span on the default tracer (and in the
    profiler's trace while one records); with telemetry disabled, a
    profiler-only span while a profiler records, else the shared no-op."""
    if _CONFIG.enabled:
        return _CONFIG.tracer.span(name, **args)
    if _profiling():
        return _ProfiledSpan(name, args)
    return NULL_SPAN


_THREAD = threading.local()


class _Phases:
    """A run of flat spans on one thread, ``<prefix>.<name>``: each
    :func:`phase` call closes the open one and opens the next, so the
    phases tile the run with no gap and no overlap between them, and
    spans opened inside a phase nest under it."""

    __slots__ = ("prefix", "args", "_open")

    def __init__(self, prefix: str, args: dict):
        self.prefix = prefix
        self.args = args
        self._open = None

    def __enter__(self):
        _THREAD.phases = self
        return self

    def __exit__(self, *exc):
        self._close(exc)
        _THREAD.phases = None
        return False

    def switch(self, name: str, args: dict):
        self._close((None, None, None))
        sp = span(f"{self.prefix}.{name}", **self.args, **args)
        sp.__enter__()
        self._open = sp
        return sp

    def _close(self, exc):
        if self._open is not None:
            sp, self._open = self._open, None
            sp.__exit__(*exc)


def phases(prefix: str, **args) -> _Phases:
    """``with phases("pipeline", batch=n):`` — run this thread's
    :func:`phase` calls as flat spans named ``<prefix>.<name>``, each
    carrying `args`; the last phase closes when the block exits."""
    return _Phases(prefix, args)


def phase(name: str, **args):
    """Close this thread's open phase and open `name`; returns its span.
    Outside a :func:`phases` block this does nothing."""
    lane = getattr(_THREAD, "phases", None)
    if lane is None:
        return NULL_SPAN
    return lane.switch(name, args)


def enabled() -> bool:
    return _CONFIG.enabled


def enable(capacity: int | None = None) -> Tracer:
    """Turn tracing on (optionally with a fresh ring of `capacity`);
    returns the active tracer."""
    if capacity is not None:
        _CONFIG.tracer = Tracer(capacity)
    _CONFIG.enabled = True
    return _CONFIG.tracer


def disable():
    _CONFIG.enabled = False


def get_tracer() -> Tracer:
    return _CONFIG.tracer


def set_tracer(tracer: Tracer):
    _CONFIG.tracer = tracer

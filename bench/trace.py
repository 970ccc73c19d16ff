"""Reduce a JAX profiler trace (``.xplane.pb``) to device numbers.

``load`` reads the file with ``jax.profiler.ProfileData`` in one pass and
keeps, per device plane (``/device:TPU:<n>``), every event of its ``XLA
Modules`` line (one per executable run) and the count and summed duration
of each operation of its ``XLA Ops`` line (a traversal's while loop puts
millions of operation events in a window, so they are not kept one by
one), and the host threads' events. The rest is plain arithmetic over
those, which ``bench/tests`` checks on recorded events.

The device counts as busy while an executable runs on it. A per-layer
metric (``bench/layer_metrics/<name>.py``) matches the executable or
operation names it needs itself: the names are the program's, and a
metric that finds none of them reports nothing.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

MODULES = "XLA Modules"
OPS = "XLA Ops"
#: operations that move data between chips, by the opcode in their HLO text
#: (``%all_to_all.14 = s32[4,256,64]{...} all-to-all(...)``)
COLLECTIVE = re.compile(r" (all-gather|all-to-all|all-reduce|reduce-scatter|"
                        r"collective-permute)(-start|-done)?\(")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Device:
    modules: list            # Event per executable run, in start order
    ops: dict                # operation name -> [count, summed ns]


@dataclasses.dataclass
class Trace:
    devices: dict            # device plane name -> Device
    host: list               # (thread line name, Event)
    window_s: float          # host-clock length of the traced window
    #: the trace stopped while work went on: each device's last executable
    #: run may be cut short, and ``modules`` leaves it out
    cut: bool = False

    def device_names(self) -> list[str]:
        return sorted(self.devices)

    def modules(self, device: str, pattern: str) -> list[Event]:
        rx = re.compile(pattern)
        runs = self.devices[device].modules
        if self.cut:
            runs = runs[:-1]
        return [e for e in runs if rx.search(e.name)]

    def op_ns(self, device: str, pattern) -> float:
        """Summed duration of the operations whose name matches."""
        rx = re.compile(pattern) if isinstance(pattern, str) else pattern
        return sum(ns for name, (_, ns) in self.devices[device].ops.items()
                   if rx.search(name))

    def busy_intervals(self, device: str) -> list[tuple[float, float]]:
        return union((e.start_ns, e.end_ns)
                     for e in self.devices[device].modules)

    def busy_s(self) -> float:
        """Seconds in which an executable ran, averaged over the devices."""
        devs = self.device_names()
        if not devs:
            return 0.0
        return sum(sum(b - a for a, b in self.busy_intervals(d))
                   for d in devs) / len(devs) * 1e-9

    def top_ops(self, n: int = 10) -> list[list]:
        """The `n` operations that took the most device time (seconds,
        averaged over the devices), named by the head of their HLO text."""
        devs = self.device_names()
        tot: dict[str, float] = {}
        for d in devs:
            for name, (_, ns) in self.devices[d].ops.items():
                tot[name] = tot.get(name, 0.0) + ns
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[short(k), v * 1e-9 / len(devs)] for k, v in top]

    def idle_gaps(self, n: int = 10) -> list[list]:
        """The `n` longest gaps between executables on the first device,
        each named by the host event that overlaps it most."""
        devs = self.device_names()
        if not devs:
            return []
        busy = self.busy_intervals(devs[0])
        gaps = sorted(((b0, a1) for (_, b0), (a1, _) in zip(busy, busy[1:])
                       if a1 > b0), key=lambda g: g[0] - g[1])[:n]
        return [[self._host_label(a, b), (b - a) * 1e-9] for a, b in gaps]

    def _host_label(self, a: float, b: float) -> str:
        best, label = 0.0, "host: nothing traced"
        for thread, e in self.host:
            # events that span the gap many times over (a thread's whole
            # life, a wait) say little about what filled it
            if e.dur_ns > 20 * (b - a):
                continue
            ov = min(b, e.end_ns) - max(a, e.start_ns)
            if ov > best:
                best, label = ov, f"{thread}: {e.name}"
        return label


def short(op: str, width: int = 96) -> str:
    """The head of an operation's HLO text: its name and result type."""
    return op if len(op) <= width else op[:width - 3] + "..."


def union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def find_xplane(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {len(found)}")
    return found[0]


def load(path: str, window_s: float, cut: bool = False) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            dev = devices[plane.name] = Device([], {})
            for line in plane.lines:
                if line.name == MODULES:
                    dev.modules = [Event(e.name, e.start_ns, e.duration_ns)
                                   for e in line.events]
                elif line.name == OPS:
                    ops = dev.ops
                    for e in line.events:
                        acc = ops.get(e.name)
                        if acc is None:
                            ops[e.name] = [1, e.duration_ns]
                        else:
                            acc[0] += 1
                            acc[1] += e.duration_ns
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((line.name, Event(e.name, e.start_ns,
                                              e.duration_ns))
                            for e in line.events)
    return Trace(devices=devices, host=host, window_s=window_s, cut=cut)

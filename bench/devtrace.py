"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device
numbers.

The harness wraps its window in a ``bench.window`` annotation and each
call into a layer in ``bench.<layer>`` (``jax.profiler.TraceAnnotation``).
From the trace this module takes:

* ``window_s``: the length of ``bench.window``, or of its part before
  the point where a device's trace buffer ran out (the profiler keeps
  some 6.3 million device events, about 2 s of the sweep's scan, whose
  every step is a few dozen events; past that the device's
  ``XLA TraceMe`` line marks what was dropped);
* ``busy_s``: the union of the intervals in which a program (an XLA
  module) ran on a device, inside the window, averaged over the
  devices;
* ``kernel_s`` and ``kernel_runs``: the device time and the count of
  the whole runs of the modules whose name holds the kernel's jitted
  function name (the sweep: ``sweep``), summed over the devices;
* ``device_ops``: the ten operations that took most device time, by
  their HLO name (a loop's time holds its body's);
* ``idle_gaps``: the ten longest stretches of the window in which no
  device ran anything, each named by the innermost host span around
  the gap's midpoint: a ``bench.*`` layer, or a span of the program's
  own recorder (``core/obs.py``) that the harness mapped onto the
  trace's clock.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per operation and their ``XLA Modules`` line one per program.
Events on a line come in start order, and each is read once.
"""
from __future__ import annotations

import heapq
import re
from pathlib import Path

WINDOW = "bench.window"
LAYER_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
CUT_LINE = "XLA TraceMe"    # where the device's trace buffer ran out


def find_trace(trace_dir: Path) -> Path | None:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    return found[-1] if found else None


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)


def read(path: Path) -> dict:
    """Host annotations and, per device, its op and module events (as
    iterators: a device line can hold millions of events)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    host, devices = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            dev = {name: _events(lines[key]) if key in lines else iter(())
                   for name, key in (("ops", OPS_LINE),
                                     ("modules", MODULES_LINE))}
            cut = [a for _, a, _ in _events(lines[CUT_LINE])] \
                if CUT_LINE in lines else []
            dev["cut_at"] = min(cut) if cut else None
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [e for e in _events(line)
                         if e[0].startswith(LAYER_PREFIX)]
    return {"host": host, "devices": devices}


class _Busy:
    """Merges one device's intervals, met in start order, into busy
    time, handing each gap between them to ``on_gap``."""

    def __init__(self, lo: float, on_gap):
        self.start = self.end = None
        self.lo, self.on_gap, self.total = lo, on_gap, 0.0

    def add(self, a: float, b: float) -> None:
        if self.start is None:
            self.on_gap(self.lo, a)
            self.start, self.end = a, b
        elif a <= self.end:
            self.end = max(self.end, b)
        else:
            self.total += self.end - self.start
            self.on_gap(self.end, a)
            self.start, self.end = a, b

    def close(self, hi: float) -> float:
        if self.start is None:
            self.on_gap(self.lo, hi)
        else:
            self.total += self.end - self.start
            self.on_gap(self.end, hi)
        return self.total


def summarize(events: dict, kernel: str = "sweep") -> dict | None:
    """The window's device numbers, or None when the trace holds no
    window or no device operation inside it.  Each device's events are
    read once.  A device is busy while one of its programs (modules)
    runs; a device whose trace has no module line counts its ops.

    ``events["program_spans"]`` (optional): the program's own host
    spans as ``(name, start, end)`` in ns from the window's start."""
    windows = [(a, b) for name, a, b in events["host"] if name == WINDOW]
    if not windows or not events["devices"]:
        return None
    lo, hi = windows[0]
    devices = [dict(d, modules=list(d["modules"]))   # a few per call
               for d in events["devices"]]
    # a cut counts where the device ran a program in the window before
    # it: the buffer filled up with that program's steps
    cuts = [d["cut_at"] for d in devices if d.get("cut_at") is not None
            and any(lo <= a < d["cut_at"] for _, a, _ in d["modules"])]
    hi = min([hi] + cuts)
    layers = [(name, a, b) for name, a, b in events["host"]
              if name != WINDOW and a < hi and b > lo]
    layers += [(name, lo + a, lo + b)
               for name, a, b in events.get("program_spans", ())]
    busy_total = kernel_ns = 0.0
    kernel_runs = 0
    per_op: dict[str, float] = {}
    gaps: list = []                        # heap of the longest gaps

    def gap(a: float, b: float) -> None:
        if b > a:
            item = (b - a, _doing(layers, 0.5 * (a + b)))
            if len(gaps) < TOP:
                heapq.heappush(gaps, item)
            elif item > gaps[0]:
                heapq.heapreplace(gaps, item)

    for dev in devices:
        busy = _Busy(lo, gap)
        saw_modules = False
        for name, a, b in dev["modules"]:
            a, b = max(a, lo), min(b, hi)
            if b <= a:
                continue
            saw_modules = True
            if kernel in name and b < hi:       # whole runs of the kernel
                kernel_ns += b - a
                kernel_runs += 1
            busy.add(a, b)
        for name, a, b in dev["ops"]:
            a, b = max(a, lo), min(b, hi)
            if b > a:
                key = name.split(" = ", 1)[0]   # "%while.5 = (...) ..."
                per_op[key] = per_op.get(key, 0.0) + (b - a)
                if not saw_modules:
                    busy.add(a, b)
        busy_total += busy.close(hi)
    if busy_total <= 0:
        return None
    n = len(devices)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_total / n / 1e9,
        "kernel_s": kernel_ns / 1e9,
        "kernel_runs": kernel_runs,
        "cut": bool(cuts),
        "device_ops": [[name, s / 1e9] for name, s in sorted(
            per_op.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[name, s / 1e9]
                      for s, name in sorted(gaps, reverse=True)],
    }


def _doing(layers, t: float) -> str:
    """The innermost host span around host time ``t``."""
    around = [(b - a, name) for name, a, b in layers if a <= t <= b]
    return min(around)[1] if around else WINDOW

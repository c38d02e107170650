"""Reduction of a profiler trace to device busy time, kernel time and
idle gaps, on the host's ``time.perf_counter`` clock.

A trace is read with ``jax.profiler.ProfileData``.  Device operations are
the events of the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane; the
device is busy in the union of their intervals.  The host writes one
annotation, :data:`ANCHOR`, right after the trace starts and reads
``time.perf_counter_ns()`` inside it; that pairs the trace's clock with
the clock of the program's spans.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Tuple

ANCHOR = "bench_clock_anchor"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class DeviceTrace:
    """Device events of one trace, in ns on the host's perf_counter clock.

    ``ops`` holds ``(name, start_ns, end_ns, text, device)`` per device
    operation, where ``text`` joins the event's string stats (op and
    module names), which kernel readers match against.  ``window`` is the
    traced window ``(start_ns, end_ns)``; ``devices`` lists the device
    planes' ordinals.
    """

    ops: List[Tuple[str, int, int, str, int]]
    window: Tuple[int, int]
    devices: List[int]

    @property
    def window_ns(self) -> int:
        return self.window[1] - self.window[0]

    def busy_intervals(self, device: int) -> List[Tuple[int, int]]:
        """The union of one device's operation intervals, clipped to the
        window."""
        lo, hi = self.window
        spans = sorted((max(s, lo), min(e, hi))
                       for _, s, e, _, d in self.ops
                       if d == device and e > lo and s < hi)
        merged: List[Tuple[int, int]] = []
        for s, e in spans:
            if merged and s <= merged[-1][1]:
                if e > merged[-1][1]:
                    merged[-1] = (merged[-1][0], e)
            else:
                merged.append((s, e))
        return merged

    def busy_ns(self) -> float:
        """Nanoseconds in which an operation ran, averaged over the
        devices traced."""
        if not self.devices:
            return 0.0
        return sum(e - s for d in self.devices
                   for s, e in self.busy_intervals(d)) / len(self.devices)

    def kernel_ns(self, pattern: str) -> Optional[int]:
        """Summed duration of the operations whose name or stats match
        ``pattern``; None when there is none."""
        rx = re.compile(pattern)
        hits = [e - s for name, s, e, text, _ in self.ops
                if rx.search(name) or rx.search(text)]
        return sum(hits) if hits else None

    def top_ops(self, n: int = 10) -> List[List]:
        """The ``n`` operations that took most device time, by HLO
        instruction name (an event is named by its whole instruction
        text, ``%name = shape op(...)``)."""
        total: Dict[str, int] = {}
        for text, s, e, _, _ in self.ops:
            name = text.split(" = ", 1)[0].lstrip("%")
            total[name] = total.get(name, 0) + (e - s)
        ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns * 1e-9] for name, ns in ranked]

    def idle_gaps(self) -> List[Tuple[int, int]]:
        """Intervals of the window in which no operation ran on the first
        device traced."""
        lo, hi = self.window
        gaps, at = [], lo
        for s, e in self.busy_intervals(self.devices[0] if self.devices
                                        else -1):
            if s > at:
                gaps.append((at, s))
            at = max(at, e)
        if hi > at:
            gaps.append((at, hi))
        return gaps


def _text(event) -> str:
    return " ".join(str(v) for _, v in event.stats if isinstance(v, str))


def reduce_trace(profile, window_perf_ns: Tuple[int, int]) -> DeviceTrace:
    """Device operations of ``profile`` (a ``ProfileData``), moved onto
    the perf_counter clock by the :data:`ANCHOR` annotation.

    ``window_perf_ns`` is the traced window on the perf_counter clock;
    the anchor event's stat ``perf_ns`` holds the perf_counter reading
    taken inside it.  Raises when the trace holds no anchor.
    """
    offset = None
    ops: List[Tuple[str, int, int, str, int]] = []
    devices: List[int] = []
    for plane in profile.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == ANCHOR:
                        perf_ns = dict(ev.stats).get("perf_ns")
                        offset = int(perf_ns) - int(ev.start_ns)
            continue
        match = DEVICE_PLANE.match(plane.name)
        if not match:
            continue
        devices.append(int(match.group(1)))
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                ops.append((ev.name, int(ev.start_ns), int(ev.end_ns),
                            _text(ev), devices[-1]))
    if offset is None:
        raise ValueError(f"no {ANCHOR} annotation in the trace")
    ops = [(n, s + offset, e + offset, t, d) for n, s, e, t, d in ops]
    return DeviceTrace(ops=ops, window=tuple(window_perf_ns),
                       devices=devices)


def label_gaps(gaps: Sequence[Tuple[int, int]], spans: Sequence,
               n: int = 10) -> List[List]:
    """The ``n`` longest idle gaps, each named by the host span that
    covers its midpoint: the innermost (shortest) one among the
    program's spans, or ``no host span`` where none does.  ``spans``
    carry ``name``, ``start_us`` and ``end_us`` on the perf_counter
    clock."""
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
    intervals = sorted((s.start_us * 1e3, s.end_us * 1e3, s.name)
                       for s in spans if s.end_us is not None)
    starts = [iv[0] for iv in intervals]
    out = []
    for lo, hi in longest:
        mid = (lo + hi) / 2
        best = None
        for s, e, name in intervals[:bisect.bisect_right(starts, mid)]:
            if s <= mid <= e and (best is None or e - s < best[0]):
                best = (e - s, name)
        out.append([best[1] if best else "no host span", (hi - lo) * 1e-9])
    return out

"""Kernels: device microseconds of ``group_match`` per device-answered
query in the traced window.

An event is the kernel's when its HLO instruction name (an event is named
by its whole instruction text, ``%name = shape op(operands)``) starts
with ``group_match``; the operands are left out, since a fusion that
reads the kernel's output names it there.  The events' intervals are
merged and clipped to the window, so each microsecond counts once; the
queries are those of the buckets inside the window, as for
``device_us_per_query``.
"""
import re

from bench.readers._inside import buckets_inside

KERNEL = re.compile(r"group_match")


def events(tr):
    """``(start_ns, end_ns)`` of the kernel's events, in start order."""
    return sorted((s, e) for name, s, e, _, _ in tr.ops
                  if KERNEL.match(name.split(" = ", 1)[0].lstrip("%")))


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr.ops:
        return None
    queries = sum(s.attrs["batch"] for s in buckets_inside(ctx))
    if not queries:
        return None
    lo, hi = tr.window
    busy, at = 0, lo
    for s, e in events(tr):
        s, e = max(s, at), min(e, hi)
        if e > s:
            busy += e - s
            at = e
    if not busy:
        return None
    return busy * 1e-3 / queries

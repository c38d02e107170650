"""Bucket programs: percent of the device passes of the buckets inside
the traced window that were overflow re-runs (``passes`` on the
``bucket`` spans: 1, or 2 where survivors overflowed the capacity)."""
from bench.readers._inside import buckets_inside


def read(ctx):
    if ctx["trace"] is None:
        return None
    passes = [s.attrs["passes"] for s in buckets_inside(ctx)
              if "passes" in s.attrs]
    if not passes:
        return None
    return 100.0 * sum(p - 1 for p in passes) / sum(passes)

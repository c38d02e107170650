"""Bucket programs: device busy microseconds per device-answered query in
the traced window."""
from bench.readers._inside import buckets_inside


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr.ops:
        return None
    queries = sum(s.attrs["batch"] for s in buckets_inside(ctx))
    if not queries:
        return None
    return tr.busy_ns() * 1e-3 / queries

"""Admission: median ``admission`` span (submit to flush pick-up), in
milliseconds."""
import statistics


def read(ctx):
    waits = [s.duration_us for s in ctx["spans"] or ()
             if s.name == "admission"]
    if not waits:
        return None
    return statistics.median(waits) * 1e-3

"""Admission: mean queries per dispatched bucket (the ``batch`` attribute
of ``bucket`` spans)."""


def read(ctx):
    fills = [s.attrs["batch"] for s in ctx["spans"] or ()
             if s.name == "bucket" and "batch" in s.attrs]
    if not fills:
        return None
    return sum(fills) / len(fills)

"""Helpers shared by the device readers: which buckets ran inside the
traced window, and a signature label's set depths."""


def buckets_inside(ctx):
    """``bucket`` spans that began and ended inside the traced window."""
    lo, hi = ctx["trace"].window
    return [s for s in ctx["spans"] or ()
            if s.name == "bucket" and s.end_us is not None
            and s.start_us * 1e3 >= lo and s.end_us * 1e3 <= hi]


def sig_ts(label):
    """Set depths ``t`` from a signature label such as ``k2/t10x12/cap1024``."""
    part = next(p for p in label.split("/") if p.startswith("t"))
    return [int(t) for t in part[1:].split("x")]

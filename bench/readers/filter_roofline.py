"""Kernels: ``bitmap_filter``'s share of its memory roofline, in percent.

The least time the filter can take is the image bytes that the answered
device queries' sets hold, read once at the chip's HBM bandwidth; the
time it took is the summed device time of its kernel's events.  Only
buckets that began and ended inside the traced window count their bytes,
while every filter event in the window counts its time, so the edges of
the window can only lower the share.
"""
from bench.readers._inside import buckets_inside, sig_ts
from bench.roofline import filter_image_bytes

KERNEL = r"_filter_kernel|bitmap_filter"


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    kernel_ns = tr.kernel_ns(KERNEL)
    if not kernel_ns:
        return None
    eng = ctx["config"]["engine"]
    nbytes = sum(s.attrs["batch"] * filter_image_bytes(
        sig_ts(s.attrs["sig"]), eng["m"], eng["w"])
        for s in buckets_inside(ctx))
    if not nbytes:
        return None
    least_s = nbytes / ctx["peak"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (kernel_ns * 1e-9)

"""Trace reduction, the roofline byte count and the peaks table."""
import gzip
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import harness, trace
from bench.readers._inside import sig_ts
from bench.roofline import filter_image_bytes

DATA = Path(__file__).parent / "data"


def _synthetic():
    ops = [("fusion.1", 100, 200, "", 0),
           ("_filter_kernel", 150, 300, "bitmap_filter", 0),
           ("fusion.2", 500, 600, "", 0),
           ("copy", 950, 1200, "", 0)]
    return trace.DeviceTrace(ops=ops, window=(0, 1000), devices=[0])


def test_busy_is_the_union_clipped_to_the_window():
    tr = _synthetic()
    assert tr.busy_intervals(0) == [(100, 300), (500, 600), (950, 1000)]
    assert tr.busy_ns() == 350
    assert tr.idle_gaps() == [(0, 100), (300, 500), (600, 950)]
    assert tr.kernel_ns("_filter_kernel") == 150
    assert tr.kernel_ns("no_such_kernel") is None
    assert tr.top_ops(2)[0][0] == "copy"


def test_gaps_are_named_by_the_innermost_host_span():
    spans = [SimpleNamespace(name="bucket", start_us=0.2, end_us=0.9),
             SimpleNamespace(name="collect", start_us=0.7, end_us=0.85)]
    named = trace.label_gaps(_synthetic().idle_gaps(), spans, n=3)
    assert named[0] == ["collect", pytest.approx(350e-9)]
    assert named[1] == ["bucket", pytest.approx(200e-9)]
    assert named[2] == ["no host span", pytest.approx(100e-9)]


def test_recorded_chip_trace_reduces():
    from jax.profiler import ProfileData

    raw = gzip.decompress((DATA / "gov2_window.xplane.pb.gz").read_bytes())
    profile = ProfileData.from_serialized_xspace(raw)
    whole = trace.reduce_trace(profile, (0, 1 << 62))
    assert whole.devices == [0] and len(whole.ops) > 0
    lo = min(s for _, s, _, _, _ in whole.ops)
    hi = max(e for _, _, e, _, _ in whole.ops)
    tr = trace.DeviceTrace(whole.ops, (lo, hi), whole.devices)
    busy = tr.busy_ns()
    assert 0 < busy < tr.window_ns
    gaps = sum(e - s for s, e in tr.idle_gaps())
    assert gaps + busy == pytest.approx(tr.window_ns)
    from bench.readers.filter_roofline import KERNEL
    assert tr.kernel_ns(KERNEL) > 0
    names = [name for name, _ in tr.top_ops(10)]
    assert any(n.startswith("group_match_pallas") for n in names)


def test_filter_bytes_count_each_set_once():
    assert filter_image_bytes([12], 2, 256) == 4096 * 2 * 32
    assert filter_image_bytes([10, 12], 2, 256) == (1024 + 4096) * 64
    assert sig_ts("k3/t9x10x10/cap256") == [9, 10, 10]


def test_peaks_table_is_keyed_by_device_kind():
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        harness.peaks("TPU v9 imaginary")

"""The readers of the flusher-loop, re-run and kernel metrics, on
synthetic spans and device traces, and the kernel's events on a recorded
chip trace."""
import gzip
import re
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import trace
from bench.readers import idle_host, match_us_per_query, rerun_share

DATA = Path(__file__).parent / "data"


def _span(name, start_ns, end_ns, **attrs):
    return SimpleNamespace(name=name, start_us=start_ns * 1e-3,
                           end_us=end_ns * 1e-3, attrs=attrs)


def _trace(ops, window=(0, 1000)):
    return trace.DeviceTrace(
        ops=[(name, s, e, "", 0) for name, s, e in ops], window=window,
        devices=[0])


def _ctx(spans, tr):
    return {"spans": spans, "trace": tr, "peak": {}, "config": {}}


# idle gaps of this trace: (0, 100), (300, 500), (600, 1000)
OPS = [("%group_match.1 = s32[8] custom-call(%a)", 100, 200),
       ("%fusion.1 = s32[8] fusion(%group_match.1)", 200, 300),
       ("%bitmap_filter.1 = s32[8] custom-call(%b)", 500, 600)]


def test_idle_host_takes_the_waits_out_of_the_idle_time():
    spans = [_span("take", 0, 10), _span("wait", 50, 150),
             _span("wait", 320, 400), _span("wait", 380, 450),
             _span("wait", 900, 2000)]
    # idle 700 ns; waits cover 50 + 130 + 100 of it
    got = idle_host.read(_ctx(spans, _trace(OPS)))
    assert got == pytest.approx(100.0 * (700 - 280) / 1000)


def test_idle_host_without_a_wait_is_the_idle_share():
    got = idle_host.read(_ctx([_span("take", 0, 10)], _trace(OPS)))
    assert got == pytest.approx(70.0)


def test_idle_host_reads_nothing_without_flusher_spans():
    assert idle_host.read(_ctx([_span("wait", 0, 100)], _trace(OPS))) is None
    assert idle_host.read(_ctx([_span("take", 0, 10)], None)) is None


def test_rerun_share_counts_passes_of_buckets_inside_the_window():
    spans = [_span("bucket", 10, 20, batch=4, passes=2),
             _span("bucket", 30, 40, batch=2, passes=1),
             _span("bucket", 50, 60, batch=1, passes=1),
             _span("bucket", 900, 1200, batch=8, passes=2),   # ends outside
             _span("bucket", 70, 80, batch=3)]                # no passes
    got = rerun_share.read(_ctx(spans, _trace(OPS)))
    assert got == pytest.approx(100.0 * 1 / 4)


def test_rerun_share_reads_nothing_without_passes():
    spans = [_span("bucket", 10, 20, batch=4)]
    assert rerun_share.read(_ctx(spans, _trace(OPS))) is None
    assert rerun_share.read(_ctx([], _trace(OPS))) is None


def test_match_us_per_query_counts_each_kernel_nanosecond_once():
    ops = OPS + [("%group_match_pallas.2 = s32[8] custom-call(%c)", 150, 250),
                 ("%group_match.3 = s32[8] custom-call(%d)", 950, 1100)]
    spans = [_span("bucket", 10, 20, batch=3), _span("bucket", 30, 40,
                                                     batch=2)]
    got = match_us_per_query.read(_ctx(spans, _trace(ops)))
    # (100, 250) merged, and (950, 1000) inside the window: 200 ns
    assert got == pytest.approx(200 * 1e-3 / 5)
    busy = _trace(ops).busy_ns()
    assert got <= busy * 1e-3 / 5


def test_match_us_per_query_reads_nothing_without_the_kernel_or_queries():
    spans = [_span("bucket", 10, 20, batch=3)]
    no_kernel = _trace([op for op in OPS if "group_match.1 =" not in op[0]])
    assert match_us_per_query.read(_ctx(spans, no_kernel)) is None
    assert match_us_per_query.read(_ctx([], _trace(OPS))) is None


def test_recorded_group_match_events_are_disjoint_and_match_top_ops():
    from jax.profiler import ProfileData

    raw = gzip.decompress((DATA / "gov2_window.xplane.pb.gz").read_bytes())
    whole = trace.reduce_trace(ProfileData.from_serialized_xspace(raw),
                               (0, 1 << 62))
    events = match_us_per_query.events(whole)
    assert events
    assert all(a[1] <= b[0] for a, b in zip(events, events[1:]))
    top = sum(ns * 1e9 for name, ns in whole.top_ops(len(whole.ops))
              if re.match("group_match", name))
    assert sum(e - s for s, e in events) == pytest.approx(top, rel=1e-9)
    # a fusion that reads the kernel's output names it among its operands
    assert any("group_match" in name.split(" = ", 1)[1]
               for name, _, _, _, _ in whole.ops)

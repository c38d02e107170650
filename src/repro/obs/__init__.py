"""Observability for the serving stack: tracing, typed metrics, export.

One :class:`Obs` object bundles the four pieces every layer reports
through:

- ``obs.registry`` — :class:`~repro.obs.registry.MetricsRegistry` with
  the serving stack's standard instruments pre-registered (see below)
  and the legacy ``EXEC_COUNTERS`` dict subsumed as a collector under
  the ``exec_`` prefix, so one ``obs.registry.snapshot()`` is a
  consistent cut of *all* telemetry, typed and legacy alike.
- ``obs.tracer`` — :class:`~repro.obs.trace.Tracer`, **disabled by
  default**: tracing costs one sentinel call per site until switched on
  (``Obs(trace=True)`` or ``obs.tracer.enabled = True``).
- ``obs.profile`` — :class:`~repro.obs.profile.ProfileStore`, fed one
  ``(ShapeSig, batch, measured_us)`` record per collected bucket; the
  CostModel-residual source for ROADMAP item 5's calibration loop.
- ``obs.ring`` — :class:`~repro.obs.export.SnapshotRing`, filled by the
  async flusher every ``snapshot_every_s``.

Standard instruments (full inventory: ``docs/OBSERVABILITY.md``):

==========================  =========  =================================
name                        type       what
==========================  =========  =================================
``queue_wait_us``           Histogram  ticket submit → flush pickup
``collect_latency_us``      Histogram  bucket dispatch → collect return
``bucket_batch_size``       Histogram  rows per executed bucket (pow2)
``bucket_survivors``        Histogram  survivors per query row (pow2)
``dispatch_failures``       Counter    buckets whose dispatch/collect
                                       raised (balancer weight released)
``inflight_buckets``        Gauge      dispatched, not yet collected
``inflight_high_water``     Gauge      max of the above since reset
``programs_compiled``       Counter    executables JAX built or loaded
                                       from the persistent cache
``compile_cache_loads``     Counter    of those, loaded from the cache
==========================  =========  =================================

The two compile counters are fed by one ``jax.monitoring`` listener per
process (:func:`_install_compile_listener`), which reports into every
live ``Obs``; those with tracing on also get a ``compile`` root span over
each compile's ``[end - secs, end]``.

Engines default to the process-global instance (:func:`get_obs`) so
``EXEC_COUNTERS``-era code and tests keep one shared telemetry world;
pass ``obs=Obs(...)`` to any engine for an isolated one.
"""
from __future__ import annotations

import threading
import time
import weakref
from typing import Dict, Optional

from repro.core.engine import EXEC_COUNTERS

from .export import (SnapshotRing, parse_json, parse_prometheus, to_json,
                     to_prometheus)
from .profile import ProfileStore, sig_label
from .registry import (Counter, Gauge, Histogram, MetricsRegistry,
                       default_latency_buckets, pow2_buckets)
from .trace import NULL_SPAN, NullSpan, Span, Tracer, format_trace

__all__ = [
    "Obs", "get_obs", "set_obs", "reset_obs",
    "MetricsRegistry", "Counter", "Gauge", "Histogram",
    "default_latency_buckets", "pow2_buckets",
    "Tracer", "Span", "NullSpan", "NULL_SPAN", "format_trace",
    "ProfileStore", "sig_label",
    "SnapshotRing", "to_prometheus", "to_json", "parse_prometheus",
    "parse_json",
]


def _exec_collector() -> Dict[str, float]:
    """The EXEC_COUNTERS compatibility shim: the legacy dict's atomic
    snapshot, re-keyed under ``exec_`` for the typed exposition."""
    return {f"exec_{k}": float(v)
            for k, v in EXEC_COUNTERS.snapshot().items()}


# every live Obs, for the process-wide compile listener (held weakly, so
# an engine's telemetry dies with it)
_live_obs: "weakref.WeakSet[Obs]" = weakref.WeakSet()
_listener_lock = threading.Lock()
_listener_installed = False
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def _on_compile(event: str, secs: float, **kw) -> None:
    """A backend compile finished (JAX reports a load from the persistent
    cache under the same event): count it in every live ``Obs`` and span
    it in those that trace."""
    if event != _COMPILE_EVENT:
        return
    end = time.perf_counter()
    for obs in list(_live_obs):
        obs.programs_compiled.inc()
        tracer = obs.tracer
        if tracer.enabled:
            tracer.span_at("compile", (end - secs) * 1e6, end * 1e6,
                           secs=round(secs, 6), fun=kw.get("fun_name"))


def _on_event(event: str, **kw) -> None:
    if event == _CACHE_HIT_EVENT:
        for obs in list(_live_obs):
            obs.compile_cache_loads.inc()


def _install_compile_listener() -> None:
    """Register the compile listeners with ``jax.monitoring``, once per
    process."""
    global _listener_installed
    with _listener_lock:
        if _listener_installed:
            return
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_on_compile)
        jax.monitoring.register_event_listener(_on_event)
        _listener_installed = True


class Obs:
    """Bundle of registry + tracer + profile store + snapshot ring."""

    def __init__(self, trace: bool = False, max_finished_spans: int = 8192,
                 ring_size: int = 64, cost_model=None):
        self.registry = MetricsRegistry()
        self.tracer = Tracer(enabled=trace,
                             max_finished=max_finished_spans)
        self.profile = ProfileStore(cost_model=cost_model)
        self.ring = SnapshotRing(maxlen=ring_size)
        self.registry.register_collector(_exec_collector)
        r = self.registry
        self.queue_wait = r.histogram(
            "queue_wait_us", "ticket submit -> flush pickup, us")
        self.collect_latency = r.histogram(
            "collect_latency_us", "bucket dispatch -> collect return, us")
        self.batch_size = r.histogram(
            "bucket_batch_size", "query rows per executed bucket",
            buckets=pow2_buckets(1, 1 << 14))
        self.survivors = r.histogram(
            "bucket_survivors", "survivors per query row",
            buckets=pow2_buckets(1, 1 << 20))
        self.dispatch_failures = r.counter(
            "dispatch_failures",
            "buckets whose dispatch or collect raised")
        self.inflight = r.gauge(
            "inflight_buckets", "dispatched, not yet collected")
        self.inflight_high_water = r.gauge(
            "inflight_high_water", "max concurrent in-flight since reset",
            track_max=True)
        self.programs_compiled = r.counter(
            "programs_compiled",
            "executables JAX built or loaded from the persistent cache")
        self.compile_cache_loads = r.counter(
            "compile_cache_loads",
            "executables loaded from the persistent compile cache")
        _live_obs.add(self)
        _install_compile_listener()

    def snapshot(self) -> Dict:
        return self.registry.snapshot()

    def trace_dump(self, trace_id: Optional[int] = None,
                   limit: int = 50) -> str:
        """Span-tree pretty-print — the stuck-flight debugging surface."""
        return self.tracer.dump(trace_id=trace_id, limit=limit)

    def reset(self) -> None:
        """Zero registry metrics, spans, profile samples, and the ring.
        Does NOT reset ``EXEC_COUNTERS`` (separate ownership, as ever)."""
        self.registry.reset()
        self.tracer.reset()
        self.profile.reset()
        self.ring.clear()


_global_lock = threading.Lock()
_global_obs: Optional[Obs] = None


def get_obs() -> Obs:
    """The process-global default ``Obs`` (tracer disabled), created on
    first use — the observability analogue of ``EXEC_COUNTERS``."""
    global _global_obs
    with _global_lock:
        if _global_obs is None:
            _global_obs = Obs(trace=False)
        return _global_obs


def set_obs(obs: Obs) -> Obs:
    """Replace the process-global default (tests / embedders)."""
    global _global_obs
    with _global_lock:
        _global_obs = obs
        return obs


def reset_obs() -> None:
    """Reset the process-global instance and discard any ``set_obs``
    override — the next :func:`get_obs` returns a fresh disabled-tracer
    default.  Test hygiene, wired into ``tests/conftest.py`` next to the
    EXEC_COUNTERS reset (engines built before the reset keep their own
    reference; only the *global fallback* is replaced)."""
    global _global_obs
    with _global_lock:
        obs = _global_obs
        _global_obs = None
    if obs is not None:
        obs.reset()

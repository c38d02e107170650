"""One cell's set-up, measured window, check and numbers.

``run.py`` drives one run from the command line; ``sweep.py`` reuses the
set-up and the window to find a configuration's knee.  Everything that
belongs to one configuration, traffic mix or per-layer metric is read
from its own file, found by the name ``BENCHMARK.json`` gives:

- ``bench/configs/<config>.json``: the deployment (sizes, posting band,
  engine settings, latency limit, knee);
- ``bench/traffic/<traffic>.json``: the mix (its request kind, the shares
  of its request shapes, the terms it draws from, distinct requests,
  rate);
- ``bench/kinds/<kind>.py``: what a request of the traffic's kind is, and
  all that depends on it: data, engine, pool, warm-up, submission, answer,
  reference and control (``bench/kinds/__init__.py``);
- ``bench/readers/<metric>.py``: the reader of a per-layer metric, where
  ``<metric>`` is the metric's name up to its first dot, so a metric
  ``plan_us.lat`` and a later ``plan_us.over`` share ``plan_us.py``.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from . import gen, kinds, reference, trace, window

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
KINDS = BENCH / "kinds"
DRAIN_S = 60.0          # how long past the close a late answer is waited for
# Batch tiers warmed for every signature.  The admission queue hands over
# a bucket whole, however far past ``flush_tier`` it grew while the host
# stalled, and a bucket past the warmed tiers compiles inside the window,
# which stalls the flusher and grows the next buckets.  So the warm-up
# covers buckets of up to 256 queries of one signature: a stall of about
# two seconds at the rates the cells offer.
WARM_TIER_MAX = 256
PRESERVE = 64           # pool requests served through the flusher in set-up


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> Dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell(name: str) -> Dict:
    """The workload entry, with its configuration and traffic files read."""
    spec = benchmark()
    work = next((w for w in spec["workloads"] if w["name"] == name), None)
    if work is None:
        raise SystemExit(f"no workload named {name!r} in BENCHMARK.json")
    conf = next(c for c in spec["configs"] if c["name"] == work["config"])
    return {
        "workload": work,
        "config": load_json(ROOT / conf["file"]),
        "traffic": load_json(BENCH / "traffic" / f"{work['traffic']}.json"),
        "end_to_end": [m for m in spec["end_to_end"]
                       if name in m.get("workloads", [name])],
        "per_layer": [m for m in spec["per_layer"]
                      if name in m.get("workloads", [name])],
    }


def peaks(device_kind: str) -> Dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"bench/peaks.json")
    return table[device_kind]


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def reader(metric: str) -> Callable:
    base = metric.split(".")[0]
    return _load(BENCH / "readers" / f"{base}.py", f"bench_reader_{base}").read


def kind_of(traffic: Dict):
    """The module of the traffic's request kind, ``<KINDS>/<kind>.py``."""
    name = traffic.get("kind", kinds.DEFAULT)
    path = KINDS / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"no request kind {name!r}: {path} is missing")
    return _load(path, f"bench_kind_{name}")


# ----------------------------------------------------------------------
# compile counting
# ----------------------------------------------------------------------


class CompileCount:
    """Executables JAX built or loaded, and the program's own trace
    counters (``EXEC_COUNTERS`` keys ending in ``_traces``)."""

    def __init__(self, counters):
        import jax.monitoring

        self.counters = counters
        self.programs = 0
        self.cache_loads = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1

    def _event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_loads += 1

    def snapshot(self) -> Dict[str, int]:
        """``programs``: executables JAX built or loaded; ``cache_loads``:
        those that came from the persistent cache (JAX reports a load
        under the same event as a compile); ``traces``: the program's
        own retrace counters."""
        traces = sum(v for k, v in self.counters.snapshot().items()
                     if k.endswith("_traces"))
        return {"programs": self.programs, "cache_loads": self.cache_loads,
                "traces": traces}

    @staticmethod
    def delta(a: Dict, b: Dict) -> Dict[str, int]:
        return {k: b[k] - a[k] for k in a}


# ----------------------------------------------------------------------
# set-up
# ----------------------------------------------------------------------


@dataclasses.dataclass
class Served:
    """A built and warmed engine with what its cell serves."""

    engine: object
    kind: object          # the request kind's module
    data: object
    pool: List
    phases: Dict[str, float]
    counts: CompileCount
    obs: object


def build(config: Dict, traffic: Dict, seed: int, trace_spans: bool,
          counts: CompileCount) -> Served:
    """Generate the data, build the engine, warm every program the log
    can run.  Prints each phase."""
    from repro.core.engine import pow2_tiers
    from repro.obs import Obs

    kind = kind_of(traffic)
    phases: Dict[str, float] = {}
    t = time.perf_counter()
    data = kind.data(config, seed)
    pool = kind.pool(traffic, data)
    phases["data_s"] = time.perf_counter() - t
    log(f"data: {len(pool)} distinct requests of kind "
        f"{traffic.get('kind', kinds.DEFAULT)} ({phases['data_s']:.3f} s)")

    obs = Obs(trace=trace_spans, max_finished_spans=4_000_000)
    engine, built = kind.engine(config, data, obs)
    phases.update(built)

    tiers = pow2_tiers(WARM_TIER_MAX)
    before = counts.snapshot()
    t = time.perf_counter()
    warmed = kind.warm(engine, pool, tiers)
    # and serve a few of the pool's requests through the flusher
    engine.start()
    try:
        for tk in [kind.submit(engine, q, None) for q in pool[:PRESERVE]]:
            tk.wait(DRAIN_S)
    finally:
        engine.stop()
    phases["warm_s"] = time.perf_counter() - t
    got = CompileCount.delta(before, counts.snapshot())
    log(f"warm-up: {warmed}; {got['programs'] - got['cache_loads']} "
        f"compiled and {got['cache_loads']} loaded from the cache "
        f"({phases['warm_s']:.3f} s)")
    return Served(engine, kind, data, pool, phases, counts, obs)


# ----------------------------------------------------------------------
# the window
# ----------------------------------------------------------------------


@dataclasses.dataclass
class Window:
    log: gen.Log
    t0: float
    resolved_s: np.ndarray
    answers: List[object]
    routes: List[Optional[str]]
    late_s: np.ndarray
    programs: Dict[str, int]
    gc: Dict
    trace: Optional[trace.DeviceTrace] = None
    spans: Optional[list] = None


def serve_window(served: Served, traffic: Dict, rate_qps: float,
                 seconds: float, seed: int, profile: bool = False,
                 keep_trace: Optional[Path] = None) -> Window:
    """Replay one log at ``rate_qps`` for ``seconds`` through ``submit``
    with the flusher running, wait for every answer (at most
    :data:`DRAIN_S` past the close), and stop the flusher.

    With ``profile`` the window runs under ``jax.profiler``, which is
    stopped once every answer is in, so that writing the trace stalls
    nothing that is measured; the reduced device trace, clipped to the
    window, is returned.  ``keep_trace`` names a file to copy the raw
    trace to.
    """
    from repro.core.engine import EXEC_COUNTERS
    from repro.serve.admission import Ticket

    engine = served.engine
    lg = gen.build_log(traffic, served.pool, rate_qps, seconds, seed)
    queries = [served.pool[i] for i in lg.which]
    EXEC_COUNTERS.reset()
    served.obs.reset()
    prof = _Profiler(keep_trace) if profile else None
    pauses = GcPauses()
    with window.Stamps(Ticket), pauses:
        engine.start()
        if prof is not None:
            prof.start()
        before = served.counts.snapshot()
        t0 = time.perf_counter() + 0.05
        replay = window.Replay(
            lambda q, arrival_at: served.kind.submit(engine, q, arrival_at),
            queries, lg.times, t0, submitters=traffic["submitters"],
            read=lambda v: (served.kind.answer(v), served.kind.route(v))
        ).start()
        replay.join(timeout=seconds + DRAIN_S)
        replay.wait_answers(t0 + seconds + DRAIN_S)
        programs = CompileCount.delta(before, served.counts.snapshot())
        if prof is not None:
            prof.stop()
        _stop(engine)
    resolved, answers, routes = replay.outcomes()
    spans = served.obs.tracer.finished() if served.obs.tracer.enabled else None
    in_window = (int(t0 * 1e9), int((t0 + seconds) * 1e9))
    return Window(lg, t0, resolved, answers, routes, replay.late_s,
                  programs, pauses.summary(),
                  prof.reduced(in_window) if prof else None, spans)


class GcPauses:
    """The interpreter's garbage collections while installed: how many of
    each generation, and the longest pause (every thread waits for one)."""

    def __enter__(self) -> "GcPauses":
        self.started: Dict[int, float] = {}
        self.pauses: List[tuple] = []
        gc.callbacks.append(self._callback)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self._callback)

    def _callback(self, phase: str, info: Dict) -> None:
        if phase == "start":
            self.started[info["generation"]] = time.perf_counter()
        elif info["generation"] in self.started:
            self.pauses.append((info["generation"], time.perf_counter()
                                - self.started.pop(info["generation"])))

    def summary(self) -> Dict:
        by_gen = {g: sum(1 for x, _ in self.pauses if x == g)
                  for g in (0, 1, 2)}
        longest = max((d for _, d in self.pauses), default=0.0)
        return {"collections": by_gen, "longest_ms": longest * 1e3,
                "total_ms": sum(d for _, d in self.pauses) * 1e3}


def _stop(engine) -> None:
    """Stop the flusher; one that does not stop within :data:`DRAIN_S` is
    left behind (a daemon) rather than hanging the run, and reported."""
    done = threading.Event()

    def stop():
        try:
            engine.stop()
        finally:
            done.set()

    threading.Thread(target=stop, daemon=True).start()
    if not done.wait(DRAIN_S):
        raise RuntimeError("the flusher did not stop within the drain time")


class _Profiler:
    """A ``jax.profiler`` trace of the window in a temporary directory,
    with the clock anchor written right after it starts."""

    def __init__(self, keep: Optional[Path] = None):
        self.keep = keep

    def start(self) -> None:
        import jax

        self.dir = tempfile.TemporaryDirectory(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir.name, profiler_options=opts)
        with jax.profiler.TraceAnnotation(trace.ANCHOR,
                                          perf_ns=time.perf_counter_ns()):
            pass

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()

    def reduced(self, window_ns) -> trace.DeviceTrace:
        from jax.profiler import ProfileData

        try:
            path = next(Path(self.dir.name).rglob("*.xplane.pb"))
            if self.keep is not None:
                shutil.copyfile(path, self.keep)
            return trace.reduce_trace(ProfileData.from_file(str(path)),
                                      window_ns)
        finally:
            self.dir.cleanup()


# ----------------------------------------------------------------------
# numbers of one run
# ----------------------------------------------------------------------


def compare_log(kind, data, lg: gen.Log, answers: List[object]):
    """Compare the answer to every request of ``lg`` with the kind's
    reference over ``data``.

    Returns ``(counts, ok)``: the numbers compared and, per request,
    whether it was answered correctly.  The control goes through here
    too, with its own answers in the program's place."""
    used = sorted(set(lg.which.tolist()))
    truth = {lg.pool[i]: kind.reference(data, lg.pool[i]) for i in used}
    queries = [lg.pool[i] for i in lg.which]
    return reference.compare(zip(queries, answers), truth)


def check(served: Served, win: Window):
    """Compare the program's answer to every request due in the window."""
    return compare_log(served.kind, served.data, win.log, win.answers)


def summary(served: Served, win: Window, ok: np.ndarray) -> Dict:
    """End-to-end numbers of the window, and those printed beside them."""
    from repro.core.engine import EXEC_COUNTERS

    due = win.log.times
    out = window.window_metrics(due, win.resolved_s, ok, win.log.seconds)
    out["late_p99_ms"] = window.nearest_rank(win.late_s, 99) * 1e3
    out["late_max_ms"] = float(win.late_s.max()) * 1e3
    quarter = [win.log.seconds * f for f in (0.25, 0.5, 0.75, 1.0)]
    out["backlog_at_quarters"] = window.backlog(due, win.resolved_s, quarter)
    snap = EXEC_COUNTERS.snapshot()
    out["counters"] = {k: v for k, v in snap.items() if v}
    out["routes"] = dict(collections.Counter(map(str, win.routes)))
    fill = served.obs.batch_size
    out["bucket_fill"] = {"buckets": fill.count,
                          "mean": fill.sum / max(1, fill.count),
                          "max_tier": fill.quantile(1.0)}
    out["window_programs"] = win.programs
    out["gc"] = win.gc
    return out


def run_cell(c: Dict, seed: int, seconds: float, traced: bool,
             t_start: float, peak: Dict) -> Dict:
    """One run of a cell, from data generation to the result line's
    object.  ``t_start`` is the perf_counter reading at process start;
    ``peak`` the device's row of the peaks table."""
    import jax
    from repro.core.engine import EXEC_COUNTERS

    config, traffic = c["config"], c["traffic"]
    counts = CompileCount(EXEC_COUNTERS)
    served = build(config, traffic, seed, traced, counts)
    win = serve_window(served, traffic, traffic["rate_qps"], seconds, seed,
                       profile=traced)
    setup_s = win.t0 + float(win.log.times[0]) - t_start
    devices = jax.devices()
    stats = devices[0].memory_stats() or {}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    compared, ok = check(served, win)
    summ = summary(served, win, ok)
    log(f"set-up: {setup_s:.3f} s; phases "
        + ", ".join(f"{k} {v:.3f}" for k, v in served.phases.items()))
    log("window: " + json.dumps(summ))
    result = {"correct": reference.verdict(compared),
              "attempted": len(win.log),
              "failed": int(len(win.log) - ok.sum()),
              "metrics": {}, "device": device}
    if not traced:
        for m in c["end_to_end"]:
            value = setup_s if m["name"] == "setup_s" else summ[m["name"]]
            result["metrics"][m["name"]] = {"value": value,
                                            "unit": m["unit"]}
    else:
        tr = win.trace
        ctx = {"spans": win.spans, "trace": tr, "peak": peak,
               "config": config}
        for m in c["per_layer"]:
            value = reader(m["name"])(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        device["busy_s"] = tr.busy_ns() * 1e-9
        device["window_s"] = tr.window_ns * 1e-9
        result["breakdown"] = {
            "device_ops": tr.top_ops(10),
            "idle_gaps": trace.label_gaps(tr.idle_gaps(), win.spans, 10)}
    result["compared"] = reference.compared_block(compared)
    for k, v in result["compared"].items():
        log(f"compared {k}: {v['value']} (limit {v['limit']})")
    return result

"""Find a configuration's knee: one set-up, then one window per offered
rate, on the chip.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 500,1000,2000 [--trace-to FILE]

Uses the cell's configuration and traffic mix but replaces the mix's rate
by each of ``--rates`` in turn (queries per second), with seed ``n + i``
for the i-th rate.  Prints one JSON line per rate: the rate served, the
latency percentiles, the backlog at each quarter of the window, how late
the generator ran, the compiles inside the window and whether every
answer matched the reference.  A rate holds when its backlog at the close
is at most a twentieth of the requests due, its ``p99_ms`` stays under the
configuration's ``latency_limit_ms``, every answer is right and nothing
compiles inside its window; the knee is the highest rate that holds with
every lower one, printed last as ``{"knee_qps": ...}`` (null when none
holds).  The sweep ends after the first rate whose backlog at the close is
over a fifth of the requests due.
``--trace-to`` profiles the first window and keeps the raw trace there.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--trace-to", type=Path, default=None)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness, reference

    c = harness.cell(args.workload)
    import jax
    from repro.compile_cache import configure_compile_cache
    from repro.core.engine import EXEC_COUNTERS

    devices = jax.devices()
    if devices[0].platform != "tpu":
        harness.log(f"no TPU: JAX found {devices[0].platform} devices")
        return 2
    configure_compile_cache()
    counts = harness.CompileCount(EXEC_COUNTERS)
    served = harness.build(c["config"], c["traffic"], args.seed, False,
                           counts)
    harness.log(f"set-up {time.perf_counter() - T_START:.3f} s")
    limit = c["config"]["latency_limit_ms"]
    knee, holding = None, True
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        win = harness.serve_window(
            served, c["traffic"], rate, args.seconds, args.seed + i,
            profile=i == 0 and args.trace_to is not None,
            keep_trace=args.trace_to)
        compared, ok = harness.check(served, win)
        summ = harness.summary(served, win, ok)
        summ.update(offered_qps=rate, compared=compared)
        if win.trace is not None:
            summ["busy_s"] = win.trace.busy_ns() * 1e-9
            summ["window_s"] = win.trace.window_ns * 1e-9
            summ["top_ops"] = win.trace.top_ops(10)
        print(json.dumps(summ), flush=True)
        holding = holding and (
            summ["backlog_at_quarters"][-1] <= 0.05 * summ["due"]
            and summ["p99_ms"] <= limit and reference.verdict(compared)
            and summ["window_programs"]["programs"] == 0)
        if holding:
            knee = rate
        if summ["backlog_at_quarters"][-1] > 0.2 * summ["due"]:
            harness.log(f"backlog grew at {rate} queries/s: sweep ends")
            break
    print(json.dumps({"knee_qps": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout.  Finds a TPU or exits non-zero, keeps
JAX's compile cache in ``<checkout>/.jax_cache``, generates the cell's
data and traffic from ``--seed``, builds and warms the engine, replays the
open-loop traffic for ``--seconds``, compares every answer with the
reference, and prints one JSON object as the last line of standard
output: the cell's end-to-end metrics (``--trace 0``) or per-layer
metrics (``--trace 1``).  Set-up by phase, routes, counters, generator
lateness and the compiles inside the window go to standard error, whose
last lines are the numbers compared beside their limits.
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
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # the compile cache lives in the checkout, whatever the machine sets:
    # a directory set from outside may be shared by the two checkouts that
    # a comparison runs side by side, and this one is not.  The first run
    # in a checkout compiles; later runs load.  JAX reads this before its
    # first compile.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    c = harness.cell(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        harness.log(f"no TPU: JAX found {devices[0].platform} devices")
        return 2
    if len(devices) < c["workload"]["chips"]:
        harness.log(f"{c['workload']['chips']} chips asked for, "
                    f"{len(devices)} found")
        return 2
    peak = harness.peaks(devices[0].device_kind)
    from repro.compile_cache import configure_compile_cache

    harness.log(f"devices: {len(devices)} x {devices[0].device_kind}; "
                f"compile cache {configure_compile_cache()}")
    result = harness.run_cell(c, args.seed, args.seconds, bool(args.trace),
                              T_START, peak)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python
"""Device time of the ``group_match`` kernel alone, on one TPU.

For each shape ``(B, S, ga, gb)`` (the first pass and the re-runs of the
``gov2-conj`` benchmark cell by default) it builds survivor groups like the
cell's: each row draws 9 distinct values for ``a`` and 9 for ``b`` from a
range of 32 of its own, the other slots ``-1`` (about 2.5 common elements per
row).  It jits ``kernels.group_intersect.group_match_pallas``, checks the
answer against ``ref.group_match_ref``, then runs ``--calls`` calls under
``jax.profiler`` and reads the ``XLA Ops`` line of the ``/device:TPU:0``
plane:

- ``kernel_us``: summed ``group_match.N`` events per call, the kernel alone
  (an event is named by its whole instruction text, ``%group_match.N = ...``);
- ``device_us``: the union of every device op's interval per call, the whole
  call with the wrapper's pads and transposes;
- ``host_us_median``: the median wall time of a call, dispatch included.

``--other DIR`` also loads ``DIR/src/repro/kernels/group_intersect.py`` (a
checkout of another commit) and times it the same way in the same process,
so both are read on the same chip.  One JSON object per shape goes to
stdout, and the list to ``--out``.  Exits 1 without a TPU, on a wrong answer,
or where a tree's calls left no ``group_match`` event.

Run, from the repo root on a machine with a TPU:
    python3 tools/kbench_group_match.py [--other DIR] [--out kbench.json]
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import re
import statistics
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.kernels import group_intersect, ref  # noqa: E402

SHAPES = [
    (1, 512, 32, 32),
    (4, 512, 32, 32),
    (1, 2048, 32, 32),
    (4, 2048, 32, 32),
    (16, 2048, 32, 32),
    (256, 2048, 32, 32),
    (1, 2048, 32, 64),
    (256, 2048, 32, 64),
]
REAL = 9       # real elements per group, as in the cell's 2^11-group sets
SPAN = 32      # values a row draws from
KERNEL = re.compile(r"group_match(\.\d+)?$")


def is_kernel(name: str) -> bool:
    """An ``XLA Ops`` event is named by its whole HLO instruction text,
    ``%name = shape op(operands)``: the kernel's when ``name`` is
    ``group_match`` or ``group_match.N``."""
    return bool(KERNEL.match(name.split(" = ", 1)[0].lstrip("%")))


def groups(rng, rows: int, g: int, base: np.ndarray) -> np.ndarray:
    """(rows, g) int32: ``REAL`` distinct values of ``[base, base + SPAN)``
    per row, the other slots -1."""
    keys = rng.random((rows, SPAN)).argsort(axis=1)[:, :REAL]
    out = np.full((rows, g), -1, np.int32)
    out[:, :REAL] = np.sort(keys, axis=1) + base[:, None]
    return out


def load_other(root: Path):
    path = root / "src" / "repro" / "kernels" / "group_intersect.py"
    spec = importlib.util.spec_from_file_location("other_group_intersect", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def device_ops(calls_fn) -> list:
    """(name, start_ns, end_ns) of the TPU:0 ops that ``calls_fn`` ran."""
    from jax.profiler import ProfileData

    with tempfile.TemporaryDirectory(prefix="kbench-trace-") as d:
        jax.profiler.start_trace(d)
        calls_fn()
        jax.profiler.stop_trace()
        path = next(Path(d).rglob("*.xplane.pb"))
        profile = ProfileData.from_file(str(path))
    ops = []
    for plane in profile.planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                ops += [
                    (ev.name, int(ev.start_ns), int(ev.end_ns))
                    for ev in line.events
                ]
    return ops


def union_ns(ops) -> int:
    total, hi = 0, None
    for _, s, e in sorted(ops, key=lambda op: op[1]):
        if hi is None or s > hi:
            total, hi = total + e - s, e
        elif e > hi:
            total, hi = total + e - hi, e
    return total


def measure(mod, a, b, want, calls: int) -> dict:
    fn = jax.jit(lambda x, y: mod.group_match_pallas(x, y, interpret=False))
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(a, b))
    compile_s = time.perf_counter() - t0
    ok = bool(np.array_equal(np.asarray(out), want))
    host = []
    for _ in range(calls):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(a, b))
        host.append(time.perf_counter() - t0)

    def run():
        for _ in range(calls):
            jax.block_until_ready(fn(a, b))

    ops = device_ops(run)
    kernel = sum(e - s for n, s, e in ops if is_kernel(n))
    return {
        "ok": ok,
        "compile_s": compile_s,
        "host_us_median": statistics.median(host) * 1e6,
        "kernel_us": kernel / calls / 1e3,
        "device_us": union_ns(ops) / calls / 1e3,
        "kernel_events": sum(1 for n, _, _ in ops if is_kernel(n)),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--other",
        type=Path,
        default=None,
        help="checkout whose group_match_pallas is timed too",
    )
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", type=Path, default=None)
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        print("kbench_group_match: no TPU", file=sys.stderr)
        return 1
    trees = {"this": group_intersect}
    if args.other is not None:
        trees["other"] = load_other(args.other)
    print("device:", jax.devices()[0].device_kind, flush=True)
    rng = np.random.default_rng(args.seed)
    rows, all_ok = [], True
    for bsz, s, ga, gb in SHAPES:
        base = np.arange(bsz * s, dtype=np.int32) * SPAN
        a = groups(rng, bsz * s, ga, base).reshape(bsz, s, ga)
        b = groups(rng, bsz * s, gb, base).reshape(bsz, s, gb)
        a, b = jnp.asarray(a), jnp.asarray(b)
        want = np.asarray(ref.group_match_ref(a, b))
        row = {"B": bsz, "S": s, "ga": ga, "gb": gb, "hits": int(want.sum())}
        for name, mod in trees.items():
            for key, val in measure(mod, a, b, want, args.calls).items():
                row[f"{name}_{key}"] = val
            # a tree whose kernel left no ``group_match`` event is a fault
            all_ok &= row[f"{name}_ok"] and row[f"{name}_kernel_events"] > 0
        print(json.dumps(row), flush=True)
        rows.append(row)
    if args.out is not None:
        args.out.parent.mkdir(parents=True, exist_ok=True)
        args.out.write_text(json.dumps(rows, indent=1))
    print("ALL_OK", all_ok)
    return 0 if all_ok else 1


if __name__ == "__main__":
    sys.exit(main())

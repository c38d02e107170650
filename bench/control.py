"""Read the control's numbers at a cell's own size.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3

For each seed the cell's data and log are generated exactly as a run
makes them, the control of the traffic's request kind (``control`` in
``bench/kinds/<kind>.py``) answers every request due in the window in the
program's place, and the comparison that decides ``correct``
(``harness.compare_log``, as in a run) counts its differences from the
reference.  Prints one JSON line per seed with the
numbers compared and the verdict.  Needs no device: the control replaces
the program.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench import gen, harness, reference

    c = harness.cell(args.workload)
    kind = harness.kind_of(c["traffic"])
    for seed in (int(s) for s in args.seeds.split(",")):
        data = kind.data(c["config"], seed)
        pool = kind.pool(c["traffic"], data)
        lg = gen.build_log(c["traffic"], pool, c["traffic"]["rate_qps"],
                           args.seconds, seed)
        ctl = {lg.pool[i]: kind.control(data, lg.pool[i])
               for i in set(lg.which.tolist())}
        counts, _ = harness.compare_log(kind, data, lg,
                                        [ctl[lg.pool[i]] for i in lg.which])
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "requests": len(lg),
                          "compared": reference.compared_block(counts),
                          "correct": reference.verdict(counts)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

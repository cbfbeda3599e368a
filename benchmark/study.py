"""The readings a cell's limits of `correct` are set from, in one
process on the chip at the cell's own size:

    python3 benchmark/study.py --workload <cell> --seeds 11,12,13

For each seed the cell's driver compares with the plain reference the
program, the control (the reference in the program's place, one
precision below what the configuration states) and each fault the cell
can have. One JSON line per seed on standard output, and all of them in
`chiprun_out/study-<cell>.jsonl`. The benchmark's own runs never call
this; `PERF.md` quotes its readings beside each limit.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="whole numbers, comma-separated")
    ap.add_argument("--seconds", type=float, default=0.0,
                    help="a serving study's window for each seed")
    args = ap.parse_args(argv)

    from benchmark import run

    ctx, driver, _, _ = run.make_context(args.workload, 0, args.seconds,
                                         False)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"study-{args.workload}.jsonl"),
              "a") as f:
        for row in driver.study(ctx, [int(s) for s in
                                      args.seeds.split(",")]):
            line = json.dumps(row)
            print(line, flush=True)
            f.write(line + "\n")
            f.flush()
    return 0


if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    sys.exit(main())

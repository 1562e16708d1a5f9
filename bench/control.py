#!/usr/bin/env python3
"""The control of a cell's comparison: the plain reference bank in the
system's place, breaking one stated guarantee, at the cell's own size.

    python3 bench/control.py --workload <cell> --seconds <s> \\
        --seeds <n> [<n> ...]

The cell's kind names the control (``control()`` in
``bench/kinds/<kind>.py``).  The bank's acknowledges each transfer
before applying it (it applies it when the same thread starts its next
operation), so an acknowledged transfer can be missing from the state:
a check has to come out above its limit on every seed.  Prints one
JSON line per seed.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run as bench  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = bench.load_cell(args.workload)
    bench.use_cache()
    device = bench.find_device(cell.entry["chips"])
    make = cell.kind.control()
    for seed in args.seeds:
        line = bench.run_cell(cell, seed=seed, seconds=args.seconds,
                              trace=False, make_system=make, device=device)
        print(json.dumps({"seed": seed,
                          "correct": line["correct"],
                          "checks": line["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

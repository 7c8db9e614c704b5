#!/usr/bin/env python3
"""Read each number compared for the program and for its control.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

Runs the cell once per seed, exactly as ``bench/run.py`` does, then replays
the window's epochs twice: against the plain reference (the program's
readings) and with the configuration's control in the program's place
(the reference with one stated guarantee broken: ``bench/ledger.py``).
Prints one JSON line per seed with both sets of readings.  The benchmark's
own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)
    code = 0
    for seed in a.seeds:
        args = run.parse(["--workload", a.workload, "--seed", str(seed),
                          "--seconds", str(a.seconds)]
                         + (["--rehearse"] if a.rehearse else []))
        try:
            res = run.run_cell(args, control=True)
        except run.NoChip as exc:
            print(f"control: {exc}", file=sys.stderr)
            return 3
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "program": res["checks"],
                          "control": res["control"]}), flush=True)
        code |= 0 if res["correct"] else 1
    return code


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Readings for the limits of a cell's check: the program's numbers and
each lower-precision control's, on several seeds in one process.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 11 12 13
    python3 bench/control.py ... --fault draft_altered

Each seed is a whole run of the cell (weights, engine, warm-up and a
window of ``--seconds``) whose sample is compared with the reference and
with every control (``reference.CONTROLS``), each held to the cell's
limits.  ``--fault`` plants one of ``bench/faults.py`` under the timed
path for every seed of the process.  One JSON line per seed on stdout.
The benchmark's own runs never run a control or a fault.
"""
from __future__ import annotations

import argparse
import builtins
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import faults, reference, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", choices=sorted(faults.FAULTS))
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run.ROOT / run.CACHE_DIR)
    sys.path.insert(0, str(run.ROOT / "src"))
    if args.fault:
        faults.FAULTS[args.fault](builtins.setattr)
    for seed in args.seeds:
        try:
            res = run.run(run.ROOT, args.workload, seed, args.seconds, False,
                          controls=tuple(reference.CONTROLS))
        except run.NoChip as e:
            run.log(f"control: {e}")
            return 2
        print(json.dumps({
            "seed": seed, "fault": args.fault, "correct": res["correct"],
            "controls_correct": {c: v["correct"]
                                 for c, v in res["controls"].items()},
            "readings": res["readings"],
            "limits": {k: v["limit"] for k, v in res["check"].items()},
            "metrics": res["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

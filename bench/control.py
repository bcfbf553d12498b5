"""Read the control of ``correct`` on the chip: for each seed, one short
run of a cell, then the widest gap of its served tokens and of the tokens
the float8 reference ranks first at the same positions.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds 5

The limit in ``bench/limits/<workload>.json`` has to lie above the first
reading on every seed and below the second: each line gives both readings
and what ``correct`` would say of each by the benchmark's own test
(``gap <= limit``).  All seeds run in one process, so the compiled programs
are shared; the benchmark's own runs never read the control.  One JSON line
per seed.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=5.0)
    a = p.parse_args(argv)
    cell = bench_run.cells.load_cell(a.workload)
    devices = bench_run.require_chip(cell.chips)
    for seed in (int(s) for s in a.seeds.split(",")):
        res = bench_run.run(cell, seed, a.seconds, False, devices,
                            control=True)
        c = res["checks"]
        gap, ctl = c["widest_gap"]["value"], c["widest_gap_control"]["value"]
        limit = c["widest_gap"]["limit"]
        print(json.dumps({"workload": a.workload, "seed": seed,
                          "program": gap, "control": ctl, "limit": limit,
                          "program_correct": res["correct"],
                          "control_correct": bench_run.within(ctl, limit)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

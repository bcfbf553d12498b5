"""Run one cell several times, each run its own process, and summarize.

    python3 bench/sets.py --workload <name> --seeds 11,12,13 --seconds 20 \
        [--trace 0|1] [--out DIR]

Runs ``bench/run.py`` once per seed, one after the other (a chip belongs to
one process at a time), writes each run's result line and standard error
under ``--out``, and prints, per metric, every value, the median and the
spread (interquartile range over the median, ``bench/stats.py``) -- the
measure the bounds in ``BENCHMARK.json`` are set from.
"""
from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from stats import spread  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True,
                   help="comma-separated seeds, one run each")
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--out", default=str(BENCH / "sets_out"))
    a = p.parse_args(argv)
    out = Path(a.out)
    out.mkdir(parents=True, exist_ok=True)
    results = []
    for seed in (int(s) for s in a.seeds.split(",")):
        tag = f"{a.workload}.s{seed}.t{a.trace}"
        t0 = time.perf_counter()
        proc = subprocess.run(
            [sys.executable, str(BENCH / "run.py"), "--workload", a.workload,
             "--seed", str(seed), "--seconds", str(a.seconds), "--trace",
             str(a.trace)], capture_output=True, text=True)
        wall = time.perf_counter() - t0
        (out / f"{tag}.err").write_text(proc.stderr)
        lines = proc.stdout.strip().splitlines()
        res = None
        if proc.returncode == 0 and lines:
            res = json.loads(lines[-1])
            (out / f"{tag}.json").write_text(lines[-1] + "\n")
        print(json.dumps({"seed": seed, "rc": proc.returncode, "wall_s": wall,
                          "result": res}), flush=True)
        if res is None:
            print(proc.stderr[-3000:], file=sys.stderr, flush=True)
        results.append(res)
    ok = [r for r in results if r]
    names = sorted({m for r in ok for m in r["metrics"]})
    for name in names:
        vals = [r["metrics"][name]["value"] for r in ok
                if name in r["metrics"]]
        med = statistics.median(vals)
        sp = spread(vals) if len(vals) >= 2 else float("nan")
        print(f"{name}: median {med} spread {sp} values {vals}", flush=True)
    gaps = [r["checks"]["widest_gap"]["value"] for r in ok]
    print(f"widest_gap: {gaps}; correct {[r['correct'] for r in ok]}",
          flush=True)
    return 0 if len(ok) == len(results) else 1


if __name__ == "__main__":
    sys.exit(main())

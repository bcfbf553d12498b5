#!/usr/bin/env python
"""repro-lint driver: run the custom static-analysis checkers (CI
``static-smoke``; docs/STATIC_ANALYSIS.md).

Four checkers prove invariants the functional tests only sample:

* ``dtype-discipline``  — dequant affine arithmetic is f32; bf16 appears
  only as a dot operand (the PR-4 rule).
* ``jit-host-boundary`` — no obs spans/metrics, ``.item()``, numpy host
  calls, or other Python side effects reachable inside jitted closures,
  scan bodies, or Pallas kernels.
* ``lock-discipline``   — shared mutable attributes of the resident
  prefetcher, block manager, and obs objects are written under their Lock
  or sit in a declared single-writer allowlist.
* ``catalog-sync``      — every obs point in the catalog has an emit site,
  every emit site is cataloged, and the codec/decoder-backend registries
  are complete.

Findings already reviewed live in ``scripts/static_baseline.json`` with a
one-line justification each; the gate is *empty delta*: any finding not in
the baseline exits 1.  ``--update-baseline`` absorbs the current findings
(then edit the justifications before committing).  If ``ruff`` is on PATH
(installed via the ``dev`` extra in CI) it runs as the generic-lint layer
and its diagnostics join the same report; locally it is skipped when absent.

Run:  python scripts/check_static.py [--checks a,b] [--json] [--no-ruff]
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path
from typing import List

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

from repro.analysis.base import (Baseline, CHECKERS, Finding, REPO_ROOT,
                                 render_json, render_text, resolve)

BASELINE_PATH = REPO_ROOT / "scripts" / "static_baseline.json"
RUFF_TARGETS = ["src", "scripts", "tests", "benchmarks"]


def run_ruff(root: Path) -> List[Finding]:
    """Generic-lint layer: ruff with the pyproject minimal config.

    Gated on availability — the container may not ship ruff (it is a dev
    extra, installed in CI); the custom checkers are the mandatory layer.
    """
    exe = shutil.which("ruff")
    if exe is None:
        print("note: ruff not on PATH; skipping generic-lint layer "
              "(CI installs it via the dev extra)", file=sys.stderr)
        return []
    targets = [t for t in RUFF_TARGETS if (root / t).exists()]
    proc = subprocess.run(
        [exe, "check", "--output-format", "json", *targets],
        cwd=root, capture_output=True, text=True)
    if proc.returncode not in (0, 1):
        return [Finding(file="<ruff>", line=0, rule="ruff",
                        message=f"ruff failed: {proc.stderr.strip()[:200]}")]
    out: List[Finding] = []
    for d in json.loads(proc.stdout or "[]"):
        path = Path(d["filename"])
        try:
            file = str(path.relative_to(root))
        except ValueError:
            file = d["filename"]
        out.append(Finding(
            file=file, line=d.get("location", {}).get("row", 0),
            rule=f"ruff/{d.get('code')}", message=d.get("message", "")))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--checks", default=None, metavar="A,B",
                    help=f"comma-separated subset of {sorted(CHECKERS)} "
                         f"(default: all)")
    ap.add_argument("--json", action="store_true",
                    help="emit machine-readable JSON instead of text")
    ap.add_argument("--baseline", default=str(BASELINE_PATH), metavar="FILE",
                    help="reviewed suppression file (default: %(default)s)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="report every finding, ignoring the baseline")
    ap.add_argument("--update-baseline", action="store_true",
                    help="absorb current findings into the baseline file "
                         "(edit the justification placeholders afterwards)")
    ap.add_argument("--no-ruff", action="store_true",
                    help="skip the generic ruff layer even if installed")
    args = ap.parse_args(argv)

    names = sorted(CHECKERS) if args.checks is None \
        else [n.strip() for n in args.checks.split(",") if n.strip()]
    for n in names:
        if n not in CHECKERS:
            ap.error(f"unknown checker {n!r}; have {sorted(CHECKERS)}")

    findings: List[Finding] = []
    counts = {}
    for n in names:
        got = resolve(n)(REPO_ROOT)
        counts[n] = len(got)
        findings.extend(got)
    if not args.no_ruff:
        got = run_ruff(REPO_ROOT)
        counts["ruff"] = len(got)
        findings.extend(got)

    baseline = Baseline() if args.no_baseline \
        else Baseline.load(Path(args.baseline))
    new, accepted, stale = baseline.split(findings)

    if args.update_baseline:
        added = baseline.absorb(new)
        for fp in stale:
            del baseline.entries[fp]
        baseline.save(Path(args.baseline))
        print(f"baseline updated: +{added} absorbed, -{len(stale)} stale "
              f"pruned -> {args.baseline}")
        return 0

    if args.json:
        print(render_json(new, extra={
            "checkers": counts,
            "accepted": len(accepted),
            "stale_baseline": stale,
        }))
    else:
        per = " ".join(f"{k}:{v}" for k, v in counts.items())
        print(f"check_static: {per} ({len(accepted)} baselined)")
        if new:
            print(render_text(new))
        for fp in stale:
            print(f"note: stale baseline entry (matches nothing): {fp}",
                  file=sys.stderr)
    if new:
        if not args.json:
            print(f"{len(new)} non-baselined finding(s) — fix them or "
                  f"baseline with a justification "
                  f"(docs/STATIC_ANALYSIS.md)", file=sys.stderr)
        return 1
    if not args.json:
        print("static-smoke: all checkers clean (empty delta vs baseline)")
    return 0


if __name__ == "__main__":
    sys.exit(main())

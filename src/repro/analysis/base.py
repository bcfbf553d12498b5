"""Shared static-analysis surface: findings, baselines, reporters, registry.

This module is the one reporting API all three repo gates speak
(``scripts/check_static.py``, ``scripts/check_docs.py``,
``scripts/check_trace.py``): a checker produces :class:`Finding` rows, a
reviewed :class:`Baseline` absorbs the accepted ones, and the reporters
print the rest as uniform ``file:line rule message`` text or ``--json``.

Deliberately stdlib-only — the ``docs-check`` CI job runs ``check_docs.py``
on a bare interpreter (no jax/numpy install), so importing this module must
never pull the scientific stack.  A checker that needs jax imports it
lazily inside its ``check()`` function; the registry maps checker names
to ``"module:function"`` strings resolved only when run.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import re
from pathlib import Path
from typing import Callable, Dict, Iterable, List, Optional, Tuple

REPO_ROOT = Path(__file__).resolve().parents[3]


@dataclasses.dataclass(frozen=True)
class Finding:
    """One structured analysis result.

    ``file`` is repo-relative; ``symbol`` names the enclosing function or
    class so the baseline fingerprint survives unrelated line drift.
    """

    file: str
    line: int
    rule: str
    message: str
    symbol: str = ""

    def fingerprint(self) -> str:
        # line numbers are deliberately excluded: a baselined finding must
        # stay baselined when code above it moves.  Messages are normalized
        # (digit runs collapsed) so counters/shapes embedded in them do not
        # churn the fingerprint either.
        msg = re.sub(r"\d+", "N", self.message)
        return f"{self.rule}::{self.file}::{self.symbol}::{msg}"

    def render(self) -> str:
        return f"{self.file}:{self.line} {self.rule} {self.message}"

    def to_json(self) -> Dict[str, object]:
        return dataclasses.asdict(self)


class Baseline:
    """Reviewed suppression file: fingerprint -> one-line justification.

    Every entry is an *accepted* finding — intentional code the checkers
    would otherwise flag — and carries a human justification string that
    code review owns.  ``split()`` partitions a fresh run into (new,
    accepted, stale); the CI gate fails on new, reports stale so dead
    entries get pruned.
    """

    def __init__(self, entries: Optional[Dict[str, str]] = None):
        self.entries: Dict[str, str] = dict(entries or {})

    @classmethod
    def load(cls, path: Path) -> "Baseline":
        if not path.exists():
            return cls()
        data = json.loads(path.read_text())
        entries = {e["fingerprint"]: e.get("justification", "")
                   for e in data.get("entries", [])}
        return cls(entries)

    def save(self, path: Path) -> None:
        data = {
            "comment": "Reviewed static-analysis suppressions "
                       "(docs/STATIC_ANALYSIS.md). Every entry needs a "
                       "justification a reviewer signed off on.",
            "entries": [
                {"fingerprint": fp, "justification": j}
                for fp, j in sorted(self.entries.items())
            ],
        }
        path.write_text(json.dumps(data, indent=2) + "\n")

    def split(self, findings: Iterable[Finding]
              ) -> Tuple[List[Finding], List[Finding], List[str]]:
        """Partition into (new, accepted) findings + stale fingerprints."""
        new: List[Finding] = []
        accepted: List[Finding] = []
        seen: set = set()
        for f in findings:
            fp = f.fingerprint()
            if fp in self.entries:
                accepted.append(f)
                seen.add(fp)
            else:
                new.append(f)
        stale = sorted(set(self.entries) - seen)
        return new, accepted, stale

    def absorb(self, findings: Iterable[Finding],
               justification: str = "TODO: justify") -> int:
        added = 0
        for f in findings:
            fp = f.fingerprint()
            if fp not in self.entries:
                self.entries[fp] = justification
                added += 1
        return added


# --------------------------------------------------------------- reporters

def render_text(findings: Iterable[Finding]) -> str:
    return "\n".join(f.render() for f in findings)


def render_json(findings: Iterable[Finding], *,
                extra: Optional[Dict[str, object]] = None) -> str:
    payload: Dict[str, object] = {"findings": [f.to_json() for f in findings]}
    if extra:
        payload.update(extra)
    return json.dumps(payload, indent=2)


# ---------------------------------------------------------------- registry

# name -> "module:function"; the callable takes the repo root Path and
# returns List[Finding].  Strings (not callables) keep this module free of
# checker imports — resolve() is the only place a checker module loads.
CHECKERS: Dict[str, str] = {
    "dtype-discipline": "repro.analysis.dtypes:check",
    "jit-host-boundary": "repro.analysis.jit_boundary:check",
    "lock-discipline": "repro.analysis.locks:check",
    "catalog-sync": "repro.analysis.catalog:check",
}


def resolve(name: str) -> Callable[[Path], List[Finding]]:
    spec = CHECKERS[name]
    mod_name, _, fn_name = spec.partition(":")
    return getattr(importlib.import_module(mod_name), fn_name)


def run_checkers(names: Iterable[str], root: Path = REPO_ROOT
                 ) -> List[Finding]:
    findings: List[Finding] = []
    for name in names:
        findings.extend(resolve(name)(root))
    return findings


# ------------------------------------------------------------ AST helpers
# (shared by the pure-AST checkers; stdlib `ast` only)

def iter_py_files(root: Path, rel_globs: Iterable[str]) -> List[Path]:
    files: List[Path] = []
    for g in rel_globs:
        files.extend(sorted(root.glob(g)))
    return [f for f in files if f.is_file()]


def rel(path: Path, root: Path) -> str:
    try:
        return str(path.relative_to(root))
    except ValueError:
        return str(path)

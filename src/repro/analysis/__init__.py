"""repro-lint: custom static analysis for the repo's unwritten invariants.

The checkers (docs/STATIC_ANALYSIS.md) turn conventions that were previously
enforced only by runtime tests — f32 dequant discipline, no host work
inside jit, lock discipline, obs-catalog sync — into
machine-checked rules gating CI before any test runs.

Import surface is deliberately tiny and stdlib-only; checker modules load
lazily via :func:`repro.analysis.base.resolve` so the docs-check job (bare
interpreter, no jax) can share the reporting API.
"""
from .base import (Baseline, CHECKERS, Finding, render_json, render_text,
                   resolve, run_checkers)

__all__ = ["Baseline", "CHECKERS", "Finding", "render_json", "render_text",
           "resolve", "run_checkers"]

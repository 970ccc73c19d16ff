"""reprolint: repo-specific static analysis gating tier-1 (DESIGN.md §9).

Three pass families over the serving/engine/kernel code:

  * concurrency  (:mod:`.locks`)   — LCK001..LCK004: guarded-by
    discipline, lock-order cycles, pin/release balance;
  * tracer hygiene (:mod:`.tracer`, :mod:`.pallas_static`) —
    TRC001..TRC004 + PLK003: control flow on tracers, kernel closure
    captures, host syncs under locks, cache-key coverage, unclamped
    kernel indexing;
  * kernel sanitizer (:mod:`.pallas_trace`, ``--strict`` only) —
    PLK001/PLK002: static VMEM footprint and race-free output index maps,
    measured by spying on real ``pl.pallas_call`` launches at the largest
    shapes each kernel declares.

The default run is stdlib-only (pure ``ast`` — it must stay importable
and fast with no jax present); ``strict=True`` adds the launch-capture
passes, which import jax and the kernel modules. The CLI lives in
``__main__`` (``python -m repro.analysis``); the runtime smoke lane in
:mod:`.smoke`.
"""
from __future__ import annotations

import os

from . import locks, pallas_static, telemetry_lint, tracer
from .astutil import SourceFile, load
from .findings import RULES, Finding, apply_suppressions

__all__ = ["analyze", "collect_files", "DEFAULT_ROOTS", "RULES", "Finding"]

_PKG = os.path.dirname(os.path.abspath(__file__))
#: default analysis root: the repro package itself
DEFAULT_ROOTS = (os.path.dirname(_PKG),)

#: path fragments never analyzed (known-bad rule fixtures live under
#: tests/analysis_fixtures — they exist to contain violations)
EXCLUDED_PARTS = ("analysis_fixtures", "__pycache__")


def collect_files(paths=None) -> list:
    """Expand files/directories into the list of .py files to lint. The
    EXCLUDED_PARTS filter applies only to directory walks — a file named
    explicitly is always linted (how the fixture tests target known-bad
    snippets)."""
    out: list = []
    for root in (paths or DEFAULT_ROOTS):
        if os.path.isfile(root):
            out.append(root)
            continue
        for dirpath, dirnames, filenames in os.walk(root):
            dirnames[:] = sorted(d for d in dirnames
                                 if d not in EXCLUDED_PARTS)
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    out.append(os.path.join(dirpath, fn))
    return out


def analyze(paths=None, *, strict: bool = False,
            budget: int | None = None) -> list:
    """Run every applicable pass; return Findings sorted by (path, line).

    Findings covered by a justified ``# reprolint: disable=`` come back
    with ``suppressed=True`` (the CLI prints but does not fail on them);
    an unjustified disable surfaces as a live SUP001.
    """
    files: list[SourceFile] = [load(p) for p in collect_files(paths)]
    findings: list[Finding] = []
    findings += locks.run(files)
    findings += tracer.run(files)
    findings += pallas_static.run(files)
    findings += telemetry_lint.run(files)
    if strict:
        from . import pallas_trace
        findings += pallas_trace.run(
            **({} if budget is None else {"budget": budget}))
    directives = {src.path: src.directives for src in files}
    findings = apply_suppressions(findings, directives)
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings

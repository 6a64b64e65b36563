"""wormlint: AST static analysis for wormhole-tpu's bug classes.

Eight checkers over ``wormhole_tpu/`` and ``tools/``:
lock-discipline, env-knobs, metric-names, jit-purity, thread-lifecycle,
retry-policy, rpc-discipline, frame-header.
See docs/static_analysis.md and ``python -m tools.wormlint --help``.
"""

from __future__ import annotations

from typing import Optional

from . import (frameheader, jitpure, knobs, locks, metricnames,
               retrypolicy, rpcdiscipline, threads)
from .core import (CHECKERS, FileSource, Finding, apply_suppressions,
                   load_baseline, load_files, match_baseline, save_baseline)

__all__ = ["CHECKERS", "FileSource", "Finding", "run_checks",
           "analyze_sources", "load_files", "load_baseline",
           "match_baseline", "save_baseline"]


def run_checks(files: list[FileSource],
               docs_text: Optional[str] = None,
               only: Optional[set[str]] = None) -> list[Finding]:
    """Run every checker (or the ``only`` subset) and apply suppressions."""
    findings: list[Finding] = []

    def want(name: str) -> bool:
        return only is None or name in only

    if want(locks.CHECKER):
        findings.extend(locks.check(files))
    if want(knobs.CHECKER):
        findings.extend(knobs.check(files, docs_text=docs_text))
    if want(metricnames.CHECKER):
        findings.extend(metricnames.check(files))
    if want(jitpure.CHECKER):
        findings.extend(jitpure.check(files))
    if want(threads.CHECKER):
        findings.extend(threads.check(files))
    if want(retrypolicy.CHECKER):
        findings.extend(retrypolicy.check(files))
    if want(rpcdiscipline.CHECKER):
        findings.extend(rpcdiscipline.check(files))
    if want(frameheader.CHECKER):
        findings.extend(frameheader.check(files))
    findings = apply_suppressions(files, findings)
    findings.sort(key=lambda f: (f.path, f.line, f.checker, f.key))
    return findings


def analyze_sources(sources: dict[str, str],
                    docs_text: Optional[str] = None,
                    only: Optional[set[str]] = None) -> list[Finding]:
    """Check in-memory sources ({path: text}); the fixture-test entry."""
    files = [FileSource(path, text) for path, text in sorted(sources.items())]
    return run_checks(files, docs_text=docs_text, only=only)

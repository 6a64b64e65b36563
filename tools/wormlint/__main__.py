"""CLI: ``python -m tools.wormlint [paths...]`` from the repo root.

Exit status is 0 iff every finding is covered by the baseline
(tools/wormlint/baseline.json). ``--json`` emits machine-readable output
for the CI gate (tests/test_lint_gate.py); ``--write-baseline`` refreshes
the baseline (preserving justifications); ``--knob-docs [group]`` prints
the registry-generated Markdown knob table used by docs/.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from . import run_checks
from .core import (FileSource, _iter_py, load_baseline, match_baseline)

_DEFAULT_ROOTS = ("wormhole_tpu", "tools")


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))


def _load(roots: list[str], root_dir: str,
          errors: list[str]) -> list[FileSource]:
    files = []
    seen = set()
    for root in roots:
        absroot = root if os.path.isabs(root) else \
            os.path.join(root_dir, root)
        for path in sorted(_iter_py(absroot)):
            rel = os.path.relpath(path, root_dir).replace(os.sep, "/")
            if rel in seen:
                continue
            seen.add(rel)
            try:
                with open(path, encoding="utf-8") as f:
                    files.append(FileSource(rel, f.read()))
            except (SyntaxError, UnicodeDecodeError, OSError) as e:
                errors.append(f"{rel}: {e}")
    files.sort(key=lambda f: f.path)
    return files


def _docs_text(root_dir: str) -> str:
    chunks = []
    docs = os.path.join(root_dir, "docs")
    if os.path.isdir(docs):
        for dirpath, _, filenames in os.walk(docs):
            for fn in sorted(filenames):
                if fn.endswith(".md"):
                    try:
                        with open(os.path.join(dirpath, fn),
                                  encoding="utf-8") as f:
                            chunks.append(f.read())
                    except OSError:
                        pass
    return "\n".join(chunks)


def _print_knob_docs(group: str) -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, _repo_root())
    from wormhole_tpu.config import knob_table_markdown
    print(knob_table_markdown(None if group == "all" else group))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tools.wormlint", description=__doc__)
    ap.add_argument("paths", nargs="*", default=list(_DEFAULT_ROOTS),
                    help="files/dirs to scan (default: %s)"
                         % " ".join(_DEFAULT_ROOTS))
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="machine-readable findings + baseline status")
    ap.add_argument("--baseline", default=None,
                    help="baseline file (default: tools/wormlint/"
                         "baseline.json)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="report every finding, ignoring the baseline")
    ap.add_argument("--write-baseline", action="store_true",
                    help="rewrite the baseline from current findings, "
                         "keeping existing justifications")
    ap.add_argument("--checker", action="append", default=None,
                    help="run only this checker (repeatable)")
    ap.add_argument("--knob-docs", nargs="?", const="all", default=None,
                    metavar="GROUP",
                    help="print the registry-generated knob table and exit")
    args = ap.parse_args(argv)

    if args.knob_docs is not None:
        return _print_knob_docs(args.knob_docs)

    root_dir = _repo_root()
    errors: list[str] = []
    files = _load(args.paths, root_dir, errors)
    only = set(args.checker) if args.checker else None
    findings = run_checks(files, docs_text=_docs_text(root_dir), only=only)

    baseline_path = args.baseline or os.path.join(
        root_dir, "tools", "wormlint", "baseline.json")
    entries = [] if args.no_baseline else load_baseline(baseline_path)

    if args.write_baseline:
        kept = {(e["checker"], e["path"], e["key"]): e["justification"]
                for e in load_baseline(baseline_path)}
        out = [{"checker": f.checker, "path": f.path, "key": f.key,
                "justification": kept.get(f.ident, "TODO: justify or fix")}
               for f in findings]
        dedup = {(e["checker"], e["path"], e["key"]): e for e in out}
        with open(baseline_path, "w", encoding="utf-8") as f:
            json.dump({"entries": list(dedup.values())}, f, indent=2)
            f.write("\n")
        print(f"wrote {len(dedup)} entries to {baseline_path}")
        return 0

    new, stale = match_baseline(findings, entries)

    if args.as_json:
        print(json.dumps({
            "findings": [f.to_dict() for f in findings],
            "new": [f.to_dict() for f in new],
            "baselined": len(findings) - len(new),
            "stale_baseline": stale,
            "parse_errors": errors,
            "files_scanned": len(files),
        }, indent=2))
    else:
        for f in new:
            print(f.render())
        for e in errors:
            print(f"warning: parse error: {e}", file=sys.stderr)
        for e in stale:
            print(f"warning: stale baseline entry "
                  f"{e['checker']}:{e['path']}:{e['key']} — fixed? remove "
                  f"it from the baseline", file=sys.stderr)
        print(f"wormlint: {len(files)} files, {len(findings)} findings "
              f"({len(findings) - len(new)} baselined, {len(new)} new)")
    return 1 if new else 0


if __name__ == "__main__":
    sys.exit(main())

"""CLI: ``python -m mxtpu.analysis <path>...`` — run tpulint (and the
program auditor).

Exit status: 0 clean, 1 findings, 2 usage error.  ``--select``/``--ignore``
filter rules; ``--list-rules`` prints the catalog; ``--stats`` appends a
per-rule count summary.  ``--format json`` emits one machine-readable JSON
document; ``--baseline FILE`` switches to ratchet mode (exit 1 only on
findings *beyond* the recorded per-(path, rule) counts; write the file with
``--write-baseline``).  ``--audit`` runs the jaxpr-level program auditor
over the canonical compiled programs instead of linting paths;
``--audit --expect-fail`` proves each audit invariant by seeding one
violation per class and requiring its detection.  The tier-1 guards
(``tests/test_analysis_guard.py``, ``tests/test_audit_guard.py``) run
``python -m mxtpu.analysis mxtpu tests`` and ``--audit`` and
assert exit 0 — the committed tree stays self-lint- and audit-clean.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List

from .lint import Finding, lint_paths
from . import rules as rules_pkg


def _counts(findings) -> Dict[str, int]:
    counts: Dict[str, int] = {}
    for f in findings:
        counts[f.rule] = counts.get(f.rule, 0) + 1
    return counts


def _baseline_counts(findings) -> Dict[str, int]:
    """Per-(path, rule) finding counts, keyed ``"path::rule"``."""
    out: Dict[str, int] = {}
    for f in findings:
        key = f"{f.path}::{f.rule}"
        out[key] = out.get(key, 0) + 1
    return out


def load_baseline(path: str) -> Dict[str, int]:
    with open(path, "r", encoding="utf-8") as fh:
        doc = json.load(fh)
    if isinstance(doc, dict) and "counts" in doc:
        doc = doc["counts"]
    if not isinstance(doc, dict):
        raise ValueError(f"baseline {path}: expected a JSON object")
    return {str(k): int(v) for k, v in doc.items()}


def diff_baseline(findings: List[Finding],
                  baseline: Dict[str, int]) -> List[Finding]:
    """Findings beyond the baseline's per-(path, rule) budget.  Count-based
    on purpose: line numbers shift on every edit, so anchoring the ratchet
    to positions would churn; a (path, rule) count only moves when a finding
    is truly added or removed."""
    new: List[Finding] = []
    budget = dict(baseline)
    for f in findings:                       # findings arrive sorted
        key = f"{f.path}::{f.rule}"
        if budget.get(key, 0) > 0:
            budget[key] -= 1
        else:
            new.append(f)
    return new


def _json_doc(findings: List[Finding], new: List[Finding] = None) -> dict:
    def enc(f: Finding) -> dict:
        return {"path": f.path, "line": f.line, "col": f.col,
                "rule": f.rule, "message": f.message}
    doc = {"version": 2,
           "findings": [enc(f) for f in findings],
           "counts": _counts(findings)}
    if new is not None:
        doc["new_findings"] = [enc(f) for f in new]
    return doc


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m mxtpu.analysis",
        description="tpulint: static checker for mxtpu's donation, "
                    "host-sync, retrace, and thread-ownership contracts — "
                    "plus the jaxpr-level program auditor (--audit)")
    parser.add_argument("paths", nargs="*",
                        help="files or directories to lint")
    parser.add_argument("--select", action="append", default=None,
                        metavar="RULE", help="only run these rule ids")
    parser.add_argument("--ignore", action="append", default=None,
                        metavar="RULE", help="skip these rule ids")
    parser.add_argument("--stats", action="store_true",
                        help="append a per-rule finding count summary")
    parser.add_argument("--list-rules", action="store_true",
                        help="print the rule catalog and exit")
    parser.add_argument("--format", choices=("text", "json"), default="text",
                        help="output format (default: text)")
    parser.add_argument("--baseline", metavar="FILE", default=None,
                        help="ratchet mode: exit nonzero only on findings "
                             "beyond this baseline's per-(path, rule) counts")
    parser.add_argument("--write-baseline", metavar="FILE", default=None,
                        help="write the current per-(path, rule) counts as a "
                             "baseline file and exit 0")
    parser.add_argument("--audit", action="store_true",
                        help="run the program auditor (shardcheck, "
                             "collective budgets, retrace closure) over the "
                             "canonical compiled programs")
    parser.add_argument("--expect-fail", action="store_true",
                        help="with --audit: seed one violation per invariant "
                             "class and require each to be detected")
    args = parser.parse_args(argv)

    if args.list_rules:
        for mod in rules_pkg.RULES:
            doc = (mod.__doc__ or "").strip().splitlines()[0]
            print(f"{mod.RULE_ID}  {mod.TITLE:<40s} {doc}")
        from . import audit as audit_mod
        for rid, title, blurb in audit_mod.rule_catalog():
            print(f"{rid}  {title:<40s} {blurb}")
        return 0

    if args.audit:
        from . import audit as audit_mod
        return audit_mod.main_audit(expect_fail=args.expect_fail,
                                    fmt=args.format,
                                    select=args.select, ignore=args.ignore)
    if args.expect_fail:
        parser.print_usage(sys.stderr)
        print("error: --expect-fail requires --audit", file=sys.stderr)
        return 2
    if not args.paths:
        parser.print_usage(sys.stderr)
        print("error: no paths given", file=sys.stderr)
        return 2

    findings = lint_paths(args.paths, select=args.select, ignore=args.ignore)

    if args.write_baseline:
        with open(args.write_baseline, "w", encoding="utf-8") as fh:
            json.dump({"version": 2, "counts": _baseline_counts(findings)},
                      fh, indent=1, sort_keys=True)
            fh.write("\n")
        print(f"baseline: {len(findings)} finding(s) across "
              f"{len(_baseline_counts(findings))} (path, rule) key(s) -> "
              f"{args.write_baseline}")
        return 0

    new = None
    if args.baseline:
        try:
            baseline = load_baseline(args.baseline)
        except (OSError, ValueError, json.JSONDecodeError) as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        new = diff_baseline(findings, baseline)

    if args.format == "json":
        print(json.dumps(_json_doc(findings, new), indent=1, sort_keys=True))
    else:
        shown = findings if new is None else new
        for f in shown:
            print(f.format())
        if args.stats:
            for rule, cnt in sorted(_counts(shown).items()):
                print(f"{rule}: {cnt} finding(s)")

    if new is not None:
        if new:
            print(f"{len(new)} new finding(s) beyond baseline "
                  f"({len(findings)} total)", file=sys.stderr)
            return 1
        return 0
    if findings:
        if args.format != "json":
            print(f"{len(findings)} finding(s)", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""CLI: python -m rocm_mpi_tpu.analysis [paths...] [options].

Exit codes: 0 clean, 1 non-suppressed, non-baselined error-severity
findings, 2 usage / missing path / unreadable baseline. Parse failures
(GL00) are reported as warnings and never fail the gate.

The repo gate (scripts/lint.sh) runs:

    python -m rocm_mpi_tpu.analysis rocm_mpi_tpu apps bench.py \
        --baseline --strict-suppressions \
        --output output/lint/findings.json

which is the whole-program interprocedural pass (per-file rules + the
GL08/GL10/GL01 engine), compared against the committed baseline, with
the stale-suppression audit on and the machine-readable findings
artifact published atomically. `--changed` restricts the reported scope to
git-dirty files plus their import-graph neighbors — the fast dev loop.
"""

from __future__ import annotations

import argparse
import sys

from rocm_mpi_tpu.analysis import baseline as baseline_mod
from rocm_mpi_tpu.analysis import core, report


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m rocm_mpi_tpu.analysis",
        description="graftlint: whole-program shard-safety analyzer "
                    "(rule catalog: docs/ANALYSIS.md)",
    )
    parser.add_argument("paths", nargs="*", help="files or directories")
    parser.add_argument("--json", action="store_true",
                        help="emit the versioned JSON document on stdout")
    parser.add_argument("--output", default=None, metavar="PATH",
                        help="also write the JSON document to PATH "
                        "(atomic tmp+rename; lint.sh banks "
                        "output/lint/findings.json)")
    parser.add_argument("--select", default=None, metavar="GL01,GL02",
                        help="run only these rule ids")
    parser.add_argument("--show-suppressed", action="store_true",
                        help="include suppressed findings in text output")
    parser.add_argument("--list-rules", action="store_true",
                        help="print the rule catalog and exit")
    parser.add_argument("--no-interprocedural", action="store_true",
                        help="per-file rules only (skip the whole-program "
                        "GL08/GL01 engine)")
    parser.add_argument("--baseline", nargs="?", metavar="PATH",
                        const=str(baseline_mod.DEFAULT_BASELINE),
                        default=None,
                        help="compare against a committed baseline: "
                        "baselined findings are reported but do not "
                        "gate (default PATH: analysis/baseline.json)")
    parser.add_argument("--baseline-write", nargs="?", metavar="PATH",
                        const=str(baseline_mod.DEFAULT_BASELINE),
                        default=None,
                        help="bank the current live findings as the "
                        "baseline and exit 0")
    parser.add_argument("--changed", action="store_true",
                        help="fast mode: lint only git-dirty files plus "
                        "their import-graph neighbors (falls back to a "
                        "full run when git state is unavailable)")
    parser.add_argument("--strict-suppressions", action="store_true",
                        help="audit suppression directives: a "
                        "`# graftlint: disable…` comment that covers no "
                        "finding at all becomes a GL99 error (dead "
                        "directives silently bless the next finding at "
                        "that site)")
    args = parser.parse_args(argv)

    if args.list_rules:
        for rule in core.catalog_rules():
            print(f"{rule.id} {rule.name} [{rule.severity}]")
            print(f"    {rule.rationale}")
        return 0
    if not args.paths:
        parser.print_usage(sys.stderr)
        print(
            "error: no paths given (the repo gate runs: "
            "python -m rocm_mpi_tpu.analysis rocm_mpi_tpu apps bench.py)",
            file=sys.stderr,
        )
        return 2

    if args.changed and args.baseline_write is not None:
        print(
            "error: --changed cannot be combined with --baseline-write "
            "(a neighborhood-restricted scan would bank a truncated "
            "ledger, silently dropping every accepted finding outside "
            "the dirty set)",
            file=sys.stderr,
        )
        return 2

    select = args.select.split(",") if args.select else None
    restrict = None
    if args.changed:
        dirty = baseline_mod.git_dirty_files()
        if dirty is None:
            print("graftlint: --changed: git state unavailable; running "
                  "the full scope", file=sys.stderr)
        else:
            try:
                entries = core.read_entries(args.paths)
            except FileNotFoundError as e:
                print(f"error: {e}", file=sys.stderr)
                return 2
            restrict = baseline_mod.expand_neighbors(entries, dirty)
    try:
        findings, files_scanned = core.lint_paths(
            args.paths, select=select, restrict=restrict,
            interprocedural=not args.no_interprocedural,
        )
    except FileNotFoundError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2

    if args.strict_suppressions:
        # Audit against the FULL findings list (suppressed included) —
        # a directive is stale only when it covers nothing at all.
        # Runs before baseline handling so GL99 findings ride the
        # reports and gate like any other error.
        findings.extend(core.audit_suppressions(
            args.paths, findings, restrict=restrict,
        ))
        findings.sort(key=lambda f: (f.file, f.line, f.col, f.rule))

    if args.baseline_write is not None:
        baseline_mod.write_baseline(args.baseline_write, findings)
        live = [
            f for f in findings
            if not f.suppressed and f.severity == "error"
        ]
        print(
            f"graftlint: banked {len(live)} finding(s) into "
            f"{args.baseline_write}",
            file=sys.stderr,
        )
        return 0

    if args.baseline is not None:
        try:
            doc = baseline_mod.load_baseline(args.baseline)
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        baseline_mod.apply_baseline(findings, doc)

    if args.output:
        report.write_findings(args.output, findings, files_scanned)
    if args.json:
        print(report.to_json(findings, files_scanned))
    else:
        print(report.to_text(findings, files_scanned,
                             show_suppressed=args.show_suppressed))
    return core.gate_exit_code(findings)


if __name__ == "__main__":
    sys.exit(main())

"""graftlint reporters: human text (with a per-rule findings table) and a
versioned JSON document for tooling (tests/test_analysis_rules.py pins the
schema; `telemetry regress --check-schema` recognizes the artifact).

JSON schema (version 3 — v2 plus the GL10 concurrency family's zero
row in counts and possible GL99 stale-suppression rows from the
--strict-suppressions audit):

    {"schema": "rmt-lint-findings",
     "version": 3,
     "files_scanned": int,
     "counts": {"GL01": int, ...},          # live (not suppressed, not
                                            # baselined), per rule
     "suppressed": int,
     "baselined": int,
     "findings": [{"file": str, "line": int, "col": int, "rule": str,
                   "severity": "error"|"warning", "message": str,
                   "hint": str, "suppressed": bool,
                   "baselined": bool}, ...]}

`write_findings` publishes the document tmp+rename — the findings
artifact is itself a schema-versioned sidecar, and GL09 would be a
hypocrite otherwise.
"""

from __future__ import annotations

import json
import os
import pathlib

from rocm_mpi_tpu.analysis.core import (
    PARSE_RULE, STALE_RULE, Finding, catalog_rules,
)

FINDINGS_SCHEMA = "rmt-lint-findings"
FINDINGS_VERSION = 3


def counts_by_rule(findings) -> dict[str, int]:
    """Live (non-suppressed, non-baselined) finding count per registered
    rule id (zero rows included so a regression report always names
    every rule)."""
    counts = {r.id: 0 for r in catalog_rules()}
    counts[PARSE_RULE] = 0
    for f in findings:
        if not f.suppressed and not f.baselined:
            counts[f.rule] = counts.get(f.rule, 0) + 1
    return counts


def findings_doc(findings, files_scanned: int) -> dict:
    return {
        "schema": FINDINGS_SCHEMA,
        "version": FINDINGS_VERSION,
        "files_scanned": files_scanned,
        "counts": counts_by_rule(findings),
        "suppressed": sum(1 for f in findings if f.suppressed),
        "baselined": sum(
            1 for f in findings if f.baselined and not f.suppressed
        ),
        "findings": [
            {
                "file": f.file,
                "line": f.line,
                "col": f.col,
                "rule": f.rule,
                "severity": f.severity,
                "message": f.message,
                "hint": f.hint,
                "suppressed": f.suppressed,
                "baselined": f.baselined,
            }
            for f in findings
        ],
    }


def to_json(findings, files_scanned: int) -> str:
    return json.dumps(findings_doc(findings, files_scanned), indent=1)


def write_findings(path, findings, files_scanned: int) -> None:
    """Publish the JSON document atomically (tmp + os.replace): the
    machine-readable artifact lint.sh banks must never be observable
    torn — GL09's own discipline."""
    path = pathlib.Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = str(path) + ".tmp"
    with open(tmp, "w") as fh:
        fh.write(to_json(findings, files_scanned))
        fh.write("\n")
    os.replace(tmp, path)


def validate_findings_doc(doc, path: str = "<doc>") -> list[str]:
    """Schema problems of one findings document (empty list = valid) —
    shared with `telemetry regress --check-schema` so a drifted reporter
    fails the gate, not the next reader."""
    problems: list[str] = []
    if not isinstance(doc, dict):
        return [f"{path}: not a JSON object"]
    if doc.get("schema") != FINDINGS_SCHEMA:
        problems.append(f"{path}: schema != {FINDINGS_SCHEMA!r}")
    if doc.get("version") != FINDINGS_VERSION:
        problems.append(f"{path}: version != {FINDINGS_VERSION}")
    for field in ("files_scanned", "suppressed", "baselined"):
        if not isinstance(doc.get(field), int):
            problems.append(f"{path}: {field} is not an int")
    counts = doc.get("counts")
    if not isinstance(counts, dict) or not all(
        isinstance(k, str) and isinstance(v, int) for k, v in counts.items()
    ):
        problems.append(f"{path}: counts is not a str->int object")
    findings = doc.get("findings")
    if not isinstance(findings, list):
        return problems + [f"{path}: findings is not a list"]
    required = {
        "file": str, "line": int, "col": int, "rule": str,
        "severity": str, "message": str, "hint": str,
        "suppressed": bool, "baselined": bool,
    }
    for i, entry in enumerate(findings):
        if not isinstance(entry, dict):
            problems.append(f"{path}: findings[{i}] is not an object")
            continue
        for field, typ in required.items():
            if not isinstance(entry.get(field), typ):
                problems.append(
                    f"{path}: findings[{i}].{field} missing or wrong type"
                )
        if entry.get("severity") not in ("error", "warning"):
            problems.append(f"{path}: findings[{i}].severity invalid")
    return problems


def rule_table(findings) -> str:
    """The per-rule findings table (printed by the self-lint test so a
    regression names the rule that fired)."""
    counts = counts_by_rule(findings)
    names = {r.id: r.name for r in catalog_rules()}
    names[PARSE_RULE] = "parse-warning"
    names[STALE_RULE] = "stale-suppression"
    width = max(len(n) for n in names.values()) + 2
    lines = ["rule   " + "name".ljust(width) + "findings"]
    for rule_id in sorted(counts):
        lines.append(
            f"{rule_id:6s} {names.get(rule_id, '?').ljust(width)}"
            f"{counts[rule_id]}"
        )
    return "\n".join(lines)


def format_finding(f: Finding) -> str:
    tag = " [suppressed]" if f.suppressed else (
        " [baselined]" if f.baselined else ""
    )
    hint = f"\n    hint: {f.hint}" if f.hint else ""
    return (
        f"{f.location()}: {f.rule} {f.severity}{tag}: {f.message}{hint}"
    )


def to_text(findings, files_scanned: int, show_suppressed: bool = False) -> str:
    shown = [f for f in findings if show_suppressed or not f.suppressed]
    lines = [format_finding(f) for f in shown]
    active = [f for f in findings if not f.suppressed and not f.baselined]
    n_sup = sum(1 for f in findings if f.suppressed)
    n_base = sum(1 for f in findings if f.baselined and not f.suppressed)
    summary = (
        f"graftlint: {files_scanned} file(s), {len(active)} finding(s)"
        + (f", {n_sup} suppressed" if n_sup else "")
        + (f", {n_base} baselined" if n_base else "")
    )
    if active:
        lines.append("")
        lines.append(rule_table(findings))
    lines.append(summary)
    return "\n".join(lines)

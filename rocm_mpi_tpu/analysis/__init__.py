"""graftlint — whole-program shard-safety static analysis for this repo.

Ten rule families, each grounded in a bug class this codebase has
actually shipped (rule catalog: docs/ANALYSIS.md):

    GL01 donation-safety        read-after-donate / async-save overlap
                                (interprocedural since v2: donating
                                callables resolve across modules)
    GL02 trace-time-purity      module-global mutation visible to traces
    GL03 compat-drift           raw jax APIs outside utils/compat+backend
    GL04 pallas-hygiene         bare refs, skipped f32 upcast, grid/BlockSpec
    GL05 collective-axis        axis names missing from the mesh
    GL06 raw-timing             perf_counter/time() outside telemetry+metrics
    GL07 signal-hygiene         signal/faulthandler outside flight+resilience
    GL08 collective-divergence  collectives under rank- or per-rank-file-
                                content-dependent control flow (whole-
                                program engine: analysis/engine.py)
    GL09 sidecar-atomicity      schema-versioned artifacts written without
                                tmp+rename / append-only discipline
    GL10 concurrency-discipline lock-guarded attrs accessed unlocked,
                                *_locked without the lock, lock-order
                                cycles, blocking under locks, serving
                                clock/sidecar-writer ownership (whole-
                                program engine: rules_concurrency.py)

Run the gate:  python -m rocm_mpi_tpu.analysis rocm_mpi_tpu apps bench.py
Suppress:      # graftlint: disable=GL01   (also disable-next=, disable-file=)
Baseline:      --baseline / --baseline-write (analysis/baseline.json)
Fast mode:     --changed (git-dirty files + import-graph neighbors)
Audit:         --strict-suppressions (dead disable directives -> GL99)

The AST side is paired with a ground-truth lowered-program audit
(`python -m rocm_mpi_tpu.analysis.lowered`): it compiles the steady-state
drivers of all three workloads and verifies the collective sequence is
identical across rank-roles and every declared donation actually aliased.

stdlib-only by design (the lowered audit is the one deliberate
exception — it imports jax, and only runs when invoked): the installed
image runs the AST gate with no optional deps, and a
repo-wide walk stays fast enough for tier-1.
"""

from rocm_mpi_tpu.analysis.core import (
    PARSE_RULE,
    Finding,
    Rule,
    all_rules,
    catalog_rules,
    gate_exit_code,
    lint_file,
    lint_paths,
    lint_source,
    source_digest,
)
from rocm_mpi_tpu.analysis.report import (
    counts_by_rule,
    findings_doc,
    rule_table,
    to_json,
    to_text,
    validate_findings_doc,
    write_findings,
)

__all__ = [
    "PARSE_RULE",
    "Finding",
    "Rule",
    "all_rules",
    "catalog_rules",
    "counts_by_rule",
    "findings_doc",
    "gate_exit_code",
    "lint_file",
    "lint_paths",
    "lint_source",
    "rule_table",
    "source_digest",
    "to_json",
    "to_text",
    "validate_findings_doc",
    "write_findings",
]

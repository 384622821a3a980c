"""Tier-1 self-lint gate: the repo's own gate scope must be graftlint-clean,
fast, and the analyzer must provably catch the two measured historical bug
classes (the acceptance oracle for the whole subsystem):

* GL01 — deleting the PR-1 donation guard (`mgr.wait_until_finished()`)
  from utils/checkpoint.py's run_segmented re-creates the async-save/
  donated-buffer overlap that corrupted every mid-run checkpoint.
* GL02 — re-adding a `pk.<KNOB> = …` module-global write to bench.py
  re-creates the trace-time mutation the old kernel-form ladder shipped.

The repo-wide run prints the per-rule findings table so a regression
names the rule that fired, and is budgeted (<5 s target, hard-capped
well above to keep CI unflaky) — safe for `not slow` tier-1.
"""

from __future__ import annotations

import pathlib
import time

from rocm_mpi_tpu.analysis import (
    gate_exit_code,
    lint_paths,
    lint_source,
    rule_table,
)

REPO = pathlib.Path(__file__).resolve().parents[1]
GATE_SCOPE = [
    str(REPO / "rocm_mpi_tpu"),
    str(REPO / "apps"),
    str(REPO / "bench.py"),
    str(REPO / "chip_smoke.py"),
]


def test_repo_is_lint_clean_and_fast():
    t0 = time.monotonic()
    findings, scanned = lint_paths(GATE_SCOPE)
    elapsed = time.monotonic() - t0
    print(f"\ngraftlint self-lint: {scanned} files in {elapsed:.2f}s")
    print(rule_table(findings))
    live = [f for f in findings if not f.suppressed]
    assert gate_exit_code(findings) == 0, (
        "graftlint gate scope is dirty:\n"
        + "\n".join(f"{f.location()}: {f.rule}: {f.message}" for f in live)
    )
    assert scanned >= 40, f"gate scope shrank to {scanned} files?"
    # <5 s is the design target; the hard cap leaves headroom for slow CI
    # boxes without letting an accidental O(n²) regress unnoticed forever.
    assert elapsed < 30.0, f"self-lint took {elapsed:.1f}s"


def test_second_walk_hits_the_ast_cache():
    lint_paths(GATE_SCOPE)  # warm (or already warm from the test above)
    t0 = time.monotonic()
    lint_paths(GATE_SCOPE)
    cached = time.monotonic() - t0
    assert cached < 2.0, f"cached repo walk took {cached:.2f}s"


# ---------------------------------------------------------------------------
# The two historical bug classes, provably caught
# ---------------------------------------------------------------------------


def test_gl01_catches_deleted_checkpoint_donation_guard():
    path = REPO / "rocm_mpi_tpu" / "utils" / "checkpoint.py"
    src = path.read_text()
    assert "mgr.wait_until_finished()" in src, (
        "the PR-1 donation guard moved — update this oracle"
    )
    mutated = "\n".join(
        line for line in src.splitlines()
        if "wait_until_finished" not in line
    )
    assert mutated != src
    before = [f for f in lint_source(src, str(path))
              if f.rule == "GL01" and not f.suppressed]
    after = [f for f in lint_source(mutated, str(path))
             if f.rule == "GL01" and not f.suppressed]
    assert before == [], "pristine checkpoint.py must be GL01-clean"
    assert after, (
        "deleting mgr.wait_until_finished() must re-create the measured "
        "async-save donation race and GL01 must catch it"
    )
    assert any("async save" in f.message for f in after)


def test_gl08_catches_deleted_uniformity_guard():
    """The interprocedural acceptance oracle: gather_to_host0's
    `process_count() == 1` early return is a UNIFORM branch (legal);
    rewriting it into a rank-dependent exit in front of the
    process_allgather re-creates the PR-6/PR-7 divergence class — one
    rank skips a host collective its peers enter — and GL08 must catch
    it."""
    path = REPO / "rocm_mpi_tpu" / "parallel" / "gather.py"
    src = path.read_text()
    assert "if jax.process_count() == 1:" in src, (
        "the gather uniformity guard moved — update this oracle"
    )
    mutated = src.replace(
        "if jax.process_count() == 1:",
        "if jax.process_index() != 0:",
    )
    before = [f for f in lint_source(src, str(path))
              if f.rule == "GL08" and not f.suppressed]
    after = [f for f in lint_source(mutated, str(path))
             if f.rule == "GL08" and not f.suppressed]
    assert before == [], "pristine gather.py must be GL08-clean"
    assert after, (
        "a rank-dependent early exit in front of process_allgather must "
        "re-create the collective-divergence hazard and GL08 must catch "
        "it"
    )
    assert any("rank-dependent" in f.message for f in after)


def test_interprocedural_pass_is_active_in_the_gate():
    """The zero-findings pin must cover the whole-program engine, not
    just the per-file rules: the gate scope linted WITHOUT the
    interprocedural pass must be missing the one accepted (suppressed)
    GL08 verdict the full pass produces — proof lint_paths actually ran
    the engine."""
    from rocm_mpi_tpu.analysis.core import lint_paths as _lint_paths

    full, _ = _lint_paths(GATE_SCOPE)
    per_file_only, _ = _lint_paths(GATE_SCOPE, interprocedural=False)
    gl08_full = [f for f in full if f.rule == "GL08"]
    gl08_flat = [f for f in per_file_only if f.rule == "GL08"]
    assert gl08_full and all(f.suppressed for f in gl08_full), (
        "the weak_scaling rung sit-out should be the one accepted GL08 "
        "verdict (suppressed with a why-comment)"
    )
    assert gl08_flat == [], (
        "per-file mode has no engine, so the cross-module verdict must "
        "vanish — if it fired here the interprocedural pin is vacuous"
    )


def test_gl02_catches_restored_bench_global_mutation():
    path = REPO / "bench.py"
    src = path.read_text()
    mutated = src + (
        "\n\nimport rocm_mpi_tpu.ops.pallas_kernels as pk\n"
        'pk.EQC_BODY_FORM = "conly"  # the pre-PR-1 ladder hazard\n'
    )
    before = [f for f in lint_source(src, "bench.py")
              if f.rule == "GL02" and not f.suppressed]
    after = [f for f in lint_source(mutated, "bench.py")
             if f.rule == "GL02" and not f.suppressed]
    assert before == [], "pristine bench.py must be GL02-clean"
    assert after and any("mutates module" in f.message for f in after)


def test_gate_scope_suppressions_all_live():
    """The --strict-suppressions pin: every disable directive in the
    gate scope still covers a finding.  A refactor that fixes (or
    moves) the suppressed code must delete its directive in the same
    change, or this test names the dead comment."""
    from rocm_mpi_tpu.analysis.core import audit_suppressions

    findings, _ = lint_paths(GATE_SCOPE)
    stale = audit_suppressions(GATE_SCOPE, findings)
    assert stale == [], "\n".join(
        f"{f.location()}: {f.message}" for f in stale
    )
    # …and the accepted verdicts those directives exist for are still
    # being produced (the audit is only meaningful against a lint run
    # that actually exercises the suppressions).
    suppressed = [f for f in findings if f.suppressed]
    assert len(suppressed) >= 6, (
        "the known accepted-verdict count shrank — if findings were "
        "fixed for real, their directives should have been deleted too"
    )


def test_fixture_dir_is_excluded_from_directory_walks():
    # The deliberately-buggy fixtures must never leak into a `tests/`-wide
    # lint invocation (e.g. someone running the CLI over the whole repo).
    findings, _ = lint_paths([str(REPO / "tests")])
    files = {f.file for f in findings}
    assert not any("analysis_fixtures" in f for f in files), files

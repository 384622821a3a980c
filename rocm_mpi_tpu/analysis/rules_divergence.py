"""GL08 — collective-divergence (interprocedural).

The bug class the last three PRs each dodged by hand: lock-step SPMD
ranks must issue the SAME collective sequence, in the same order, every
step — a rank that skips (or adds) an exchange leaves its neighbor
blocked inside a collective that will never complete. Not an error, a
distributed hang, and the per-file rules cannot see it because the
divergence spans functions and modules:

* **PR 7 (autotune):** under multi-controller jax every process resolves
  the tuning cache from ITS OWN filesystem; a per-rank winning `chunk`
  builds divergently traced scan programs — mismatched collective
  counts per invocation. Shipped fix: `process_count() > 1` → defaults
  (models/driver.auto_scan_chunk, parallel/deep_halo.auto_deep_k).
* **PR 6 (elastic restore):** resuming on a different mesh must rebuild
  the exchange machinery identically on every rank; a rank that
  branches on locally-read manifest content into a different
  rebuild-vs-reuse path issues a different warmup sequence.

What fires (engine.check_divergence walks the flow with the program
summaries):

* a collective — or a call whose summary transitively contains
  collectives, e.g. a halo exchange or a model step — reachable under
  control flow whose test is **rank-dependent** (`process_index`,
  `axis_index`, or a value returned by a function summarized as
  rank-dependent);
* the same, under a test that is **file-content-dependent** (values
  from `open/json.load/read_text` or functions summarized as file
  readers), unless the path is proven single-controller;
* branch arms whose collective **sequences differ** (one arm's sequence
  is compared against the other's, transitively) — equal sequences on
  both arms are legal however the test is tainted;
* a rank/file-dependent **early exit** (`if process_index() != 0:
  return`) followed by collectives in the continuation — the exact
  shape of a rank-0-only rebuild.

What never fires: branches on `process_count()` (uniform on every
rank), decisions laundered through `broadcast_one_to_all` /
`process_allgather` (their results are uniform by construction — the
blessed fix), rank-guarded host-only work (manifest writes, logging),
and anything the resolver cannot see (docs/ANALYSIS.md "can and cannot
see": a miss is never a false positive).
"""

from __future__ import annotations

from rocm_mpi_tpu.analysis.core import ModuleContext, Rule


class DivergenceRule(Rule):
    id = "GL08"
    name = "collective-divergence"
    severity = "error"
    rationale = (
        "SPMD ranks issuing different collective sequences deadlock; "
        "rank- or per-rank-file-content-dependent control flow around a "
        "collective is the PR-6/PR-7 hazard class, visible only "
        "interprocedurally"
    )
    hint = "see docs/ANALYSIS.md#gl08"

    def check(self, ctx: ModuleContext):
        """Single-module fallback (the whole-program pass in
        engine.analyze_modules is the real engine; this treats the one
        file as a one-module program so fixtures and ad-hoc
        lint_source calls still get the rule)."""
        from rocm_mpi_tpu.analysis import engine

        mod = engine.ModuleInfo(
            path=ctx.path,
            name=engine.module_name_for_path(ctx.path),
            source=ctx.source,
            tree=ctx.tree,
        )
        program = engine.Program([mod])
        return engine.check_divergence(self, ctx, program, mod)

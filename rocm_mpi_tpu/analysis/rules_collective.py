"""GL05 — collective/axis-name consistency inside shard_map bodies.

A `psum`/`ppermute`/`axis_index` over an axis name that is not in the
surrounding mesh raises at trace time *on the sharded path only* — CPU
tests that exercise a 1-device mesh or the GSPMD variant never touch it,
so the typo ships to the chip session (where every failed trace costs
chip time; SURVEY.md §0's whole point is that the comms engineering is
hand-tuned and easy to get quietly wrong).

Statically checkable slice: for functions passed to `shard_map` in this
module, every *literal* axis-name argument of a collective must appear in
the module's literal axis vocabulary — names in `Mesh(...)` /
`PartitionSpec(...)` / `P(...)` calls, `axis_name(s)=` kwargs, and
`AXIS_NAMES`-style module constants. Variables (the common in-tree case:
`grid.axis_names[ax]`) are skipped — the rule only judges what it can see.
Modules with no axis literals at all are skipped entirely.

The `batch` axis vocabulary (PR 13, docs/SERVING.md): on a space×batch
mesh the leading `batch` axis carries INDEPENDENT simulation lanes —
separate tenants. A permutation-family collective (`ppermute`,
`pshuffle`, `all_to_all`) over the literal `batch` axis moves one
tenant's state into another's lane — a cross-tenant leak no 1-device
CPU test ever executes — so it is a finding even though `batch` is in
the mesh vocabulary. Reductions (`psum`/`pmean`/…) over `batch` stay
clean: cross-lane diagnostics are legitimate.
"""

from __future__ import annotations

import ast

from rocm_mpi_tpu.analysis import astutil
from rocm_mpi_tpu.analysis.core import ModuleContext, Rule

_COLLECTIVES = {
    "psum", "pmean", "pmax", "pmin", "ppermute", "pshuffle", "all_gather",
    "all_to_all", "psum_scatter", "axis_index", "axis_size",
}

# The permutation family: collectives that MOVE state between mesh
# positions (vs reducing over them). Over the multi-tenant lane axis
# that is a cross-tenant leak (parallel.mesh.BATCH_AXIS contract).
_PERMUTING = {"ppermute", "pshuffle", "all_to_all"}
_BATCH_AXIS = "batch"  # literal twin of parallel.mesh.BATCH_AXIS


def _module_axis_vocabulary(tree: ast.Module) -> set[str]:
    vocab: set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            callee = astutil.tail_name(astutil.call_name(node))
            if callee == "Mesh" and len(node.args) >= 2:
                vocab.update(astutil.str_args(node.args[1]))
            elif callee in ("PartitionSpec", "P"):
                for arg in node.args:
                    vocab.update(astutil.str_args(arg))
            for kw in node.keywords:
                if kw.arg in ("axis_name", "axis_names"):
                    vocab.update(astutil.str_args(kw.value))
        elif isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                isinstance(node.targets[0], ast.Name) and \
                "AXIS" in node.targets[0].id.upper():
            vocab.update(astutil.str_args(node.value))
    return vocab


class AxisConsistencyRule(Rule):
    id = "GL05"
    name = "collective-axis-consistency"
    severity = "error"
    rationale = (
        "a collective over an axis name missing from the mesh only fails "
        "on the sharded trace — 1-device CPU tests never reach it, so the "
        "typo surfaces mid-chip-session"
    )
    hint = "see docs/ANALYSIS.md#gl05"

    def check(self, ctx: ModuleContext):
        vocab = _module_axis_vocabulary(ctx.tree)
        if not vocab:
            return []
        findings = []
        for traced in astutil.traced_bodies(ctx.tree):
            if traced.kind != "shard_map":
                continue
            for node in astutil.walk_no_nested_functions(traced.fn):
                if not isinstance(node, ast.Call):
                    continue
                callee = astutil.tail_name(astutil.call_name(node))
                if callee not in _COLLECTIVES:
                    continue
                literal_axes = []
                for arg in node.args:
                    literal_axes.extend(astutil.str_args(arg))
                for kw in node.keywords:
                    if kw.arg == "axis_name":
                        literal_axes.extend(astutil.str_args(kw.value))
                for axis in literal_axes:
                    if axis not in vocab:
                        findings.append(ctx.finding(
                            node, self,
                            f"collective '{callee}' over axis '{axis}' "
                            f"inside shard_map body '{traced.fn.name}', "
                            "but this module's mesh/spec axis names are "
                            f"{sorted(vocab)}",
                            "use an axis name from the mesh (or thread "
                            "grid.axis_names through instead of a "
                            "literal)",
                        ))
                    elif axis == _BATCH_AXIS and callee in _PERMUTING:
                        findings.append(ctx.finding(
                            node, self,
                            f"halo/permutation collective '{callee}' over "
                            f"the '{_BATCH_AXIS}' lane axis inside "
                            f"shard_map body '{traced.fn.name}' — lanes "
                            "are independent tenants (docs/SERVING.md); "
                            "permuting state across the batch axis leaks "
                            "one simulation into another",
                            "halo collectives belong on the space axes "
                            "only (reductions like psum over 'batch' — "
                            "cross-lane diagnostics — are fine)",
                        ))
        return findings

"""GL03 — compat drift.

The installed jax is 0.9.0, and the code supports that one version. Every
"where does this live / what is it called" question is answered exactly
once, in `rocm_mpi_tpu/utils/compat.py` (API spellings) and
`rocm_mpi_tpu/utils/backend.py` (backend knobs), so the next version bump
is fixed in one place. A call site that goes to `jax.experimental` /
version-specific spellings directly re-introduces per-call-site drift —
the class of bug that made the seed's tier-1 suite fail collection
outright before PR 1 centralized the shims.

Checked spellings (each with its owning chokepoint, which is allowlisted):

* any `jax.experimental` import or attribute chain   -> utils.compat
* `from jax import shard_map` / `jax.shard_map`      -> utils.compat.shard_map
* `<compiled>.cost_analysis()` method calls          -> utils.compat.cost_analysis_dict
* `jax.config.update("jax_num_cpu_devices", …)`      -> utils.backend.set_cpu_device_count
* `lax.axis_size` attribute use                      -> utils.compat.axis_size
* `ShapeDtypeStruct(..., vma=…)`                     -> utils.compat.out_struct_like
"""

from __future__ import annotations

import ast

from rocm_mpi_tpu.analysis import astutil
from rocm_mpi_tpu.analysis.core import ModuleContext, Rule

# Files allowed to touch the raw APIs: the chokepoints themselves.
_COMPAT_OWNERS = ("rocm_mpi_tpu/utils/compat.py",)
_BACKEND_OWNERS = (
    "rocm_mpi_tpu/utils/compat.py",
    "rocm_mpi_tpu/utils/backend.py",
)


def _owned_by(ctx: ModuleContext, owners) -> bool:
    return ctx.posix_path.endswith(owners)


class CompatDriftRule(Rule):
    id = "GL03"
    name = "compat-drift"
    severity = "error"
    rationale = (
        "the installed jax (0.9.0) is reached through one chokepoint "
        "(shard_map home, check_vma, cost_analysis shape, "
        "jax_num_cpu_devices): utils/compat.py + utils/backend.py; direct "
        "use re-opens the per-call-site drift that broke the seed's test "
        "collection"
    )
    hint = "see docs/ANALYSIS.md#gl03"

    def check(self, ctx: ModuleContext):
        findings = []
        in_compat = _owned_by(ctx, _COMPAT_OWNERS)
        in_backend_owner = _owned_by(ctx, _BACKEND_OWNERS)

        for node in ast.walk(ctx.tree):
            # ---- imports -------------------------------------------------
            if isinstance(node, ast.Import) and not in_compat:
                for alias in node.names:
                    if alias.name.split(".")[:2] == ["jax", "experimental"]:
                        findings.append(ctx.finding(
                            node, self,
                            f"direct import of '{alias.name}' — "
                            "jax.experimental contents move between "
                            "versions",
                            "import the shim from "
                            "rocm_mpi_tpu.utils.compat instead",
                        ))
            elif isinstance(node, ast.ImportFrom) and node.module \
                    and not in_compat:
                mod = node.module
                if mod == "jax.experimental" or \
                        mod.startswith("jax.experimental."):
                    findings.append(ctx.finding(
                        node, self,
                        f"direct import from '{mod}' — jax.experimental "
                        "contents move between versions",
                        "import the shim from rocm_mpi_tpu.utils.compat "
                        "instead (it owns pallas/pallas_tpu/"
                        "multihost_utils/shard_map resolution)",
                    ))
                elif mod == "jax" and any(
                        a.name == "experimental" for a in node.names):
                    findings.append(ctx.finding(
                        node, self,
                        "direct import of jax.experimental",
                        "route through rocm_mpi_tpu.utils.compat",
                    ))
                elif mod == "jax" and any(
                        a.name == "shard_map" for a in node.names):
                    findings.append(ctx.finding(
                        node, self,
                        "shard_map imported from jax directly — its home "
                        "and check_vma/check_rep kwarg differ across "
                        "versions",
                        "use rocm_mpi_tpu.utils.compat.shard_map (renames "
                        "the replication-check kwarg to match the "
                        "installed jax)",
                    ))
            # ---- attribute chains ---------------------------------------
            elif isinstance(node, ast.Attribute):
                dotted = astutil.dotted_name(node) or ""
                # fire once per chain, on the innermost jax.experimental
                if dotted == "jax.experimental" and not in_compat:
                    findings.append(ctx.finding(
                        node, self,
                        "direct use of the jax.experimental namespace",
                        "route through rocm_mpi_tpu.utils.compat",
                    ))
                elif dotted == "jax.shard_map" and not in_compat:
                    findings.append(ctx.finding(
                        node, self,
                        "jax.shard_map used directly",
                        "use rocm_mpi_tpu.utils.compat.shard_map",
                    ))
                elif dotted.endswith("lax.axis_size") and not in_compat:
                    findings.append(ctx.finding(
                        node, self,
                        "lax.axis_size used directly",
                        "use rocm_mpi_tpu.utils.compat.axis_size",
                    ))
            # ---- calls ---------------------------------------------------
            elif isinstance(node, ast.Call):
                fn = node.func
                if isinstance(fn, ast.Attribute) and \
                        fn.attr == "cost_analysis" and not in_compat:
                    findings.append(ctx.finding(
                        node, self,
                        ".cost_analysis() called directly — its return "
                        "shape has changed across jax versions",
                        "use rocm_mpi_tpu.utils.compat.cost_analysis_dict",
                    ))
                elif isinstance(fn, ast.Attribute) and fn.attr == "update" \
                        and not in_backend_owner:
                    if node.args and astutil.str_const(node.args[0]) == \
                            "jax_num_cpu_devices":
                        findings.append(ctx.finding(
                            node, self,
                            "jax_num_cpu_devices set directly (the "
                            "virtual-CPU-mesh knob has one owner)",
                            "use rocm_mpi_tpu.utils.backend."
                            "set_cpu_device_count",
                        ))
                elif astutil.tail_name(astutil.call_name(node)) == \
                        "ShapeDtypeStruct" and not in_compat:
                    if astutil.call_kwarg(node, "vma") is not None:
                        findings.append(ctx.finding(
                            node, self,
                            "ShapeDtypeStruct(vma=…) spelled directly — "
                            "vma tracking is version-specific",
                            "use rocm_mpi_tpu.utils.compat.out_struct_like",
                        ))
        return findings

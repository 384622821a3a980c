"""GL06 — raw timing outside the measurement chokepoints.

The telemetry subsystem exists because scattered `time.perf_counter()`
pairs produce walltime numbers with no sync discipline (jax dispatch is
async — an unsynced interval times the *enqueue*, not the work) and no
destination (the number is printed and lost
instead of landing in the per-rank stream the aggregation/regression
tooling reads). `time.time()` has the same two problems plus wall-clock
jumps.

The rule flags calls to `time.perf_counter[_ns]()` and `time.time[_ns]()`
— by module attribute or `from time import …` alias — everywhere except
the two owners that implement the discipline:

* `rocm_mpi_tpu/telemetry/`   (spans/events own the clock reads)
* `rocm_mpi_tpu/utils/metrics.py` (Timer + force, the sync-correct pair)

`time.monotonic()` is deliberately NOT flagged: the launcher's
supervision heartbeats and bench.py's budget bookkeeping are wall-clock
*control flow* (deadlines), not measurements, and monotonic is the right
tool there. `time.sleep` is obviously fine. The fix for a finding is a
telemetry span, a labeled `metrics.Timer`, or — for a genuine new
measurement primitive — moving the code into an owner.
"""

from __future__ import annotations

import ast

from rocm_mpi_tpu.analysis import astutil
from rocm_mpi_tpu.analysis.core import ModuleContext, Rule

_OWNER_FILES = (
    "rocm_mpi_tpu/utils/metrics.py",
)
_OWNER_DIR_MARK = "/rocm_mpi_tpu/telemetry/"

_FLAGGED = frozenset({"perf_counter", "perf_counter_ns", "time", "time_ns"})


def _is_owner(ctx: ModuleContext) -> bool:
    return (
        ctx.posix_path.endswith(_OWNER_FILES)
        or _OWNER_DIR_MARK in ctx.posix_path
    )


class RawTimingRule(Rule):
    id = "GL06"
    name = "raw-timing"
    severity = "error"
    rationale = (
        "bare time.perf_counter()/time.time() timing has no sync "
        "discipline (async dispatch: it times the enqueue, not the work) "
        "and bypasses the telemetry stream; use telemetry.span / a "
        "labeled metrics.Timer (owners: utils/metrics.py, telemetry/)"
    )
    hint = "see docs/ANALYSIS.md#gl06"

    def check(self, ctx: ModuleContext):
        if _is_owner(ctx):
            return []
        imports = astutil.collect_imports(ctx.tree)
        # Local aliases bound to the time module / its flagged functions.
        time_modules = {
            local for local, mod in imports.module_aliases.items()
            if mod == "time"
        }
        flagged_names = {
            local: origin.rpartition(".")[2]
            for local, origin in imports.from_imports.items()
            if origin in {f"time.{fn}" for fn in _FLAGGED}
        }
        findings = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            spelled = None
            if (
                isinstance(fn, ast.Attribute)
                and isinstance(fn.value, ast.Name)
                and fn.value.id in time_modules
                and fn.attr in _FLAGGED
            ):
                spelled = f"{fn.value.id}.{fn.attr}"
            elif isinstance(fn, ast.Name) and fn.id in flagged_names:
                spelled = f"{fn.id} (= time.{flagged_names[fn.id]})"
            if spelled is not None:
                findings.append(ctx.finding(
                    node, self,
                    f"raw {spelled}() timing outside the measurement "
                    "chokepoints — unsynced against async dispatch and "
                    "invisible to telemetry",
                    "wrap the interval in telemetry.span(...) or a "
                    "labeled utils.metrics.Timer (both sync via the "
                    "block_until_ready force())",
                ))
        return findings

"""Compile observability: count compilations and cache misses per
program, and pin "zero recompiles after warmup".

Why this is a health-plane concern: the framework's perf story (PR 4's
donation-aware scan drivers, the fused halo/interior step) assumes each
steady-state program compiles ONCE. A shape change, a dtype drift, or a
non-hashable static arg quietly re-triggers XLA per step instead — a
recompile storm that looks like "the run got slow" or "the run hung".
Nothing in the PR-3 stream recorded
compiles at all, so the storm was invisible.

The hook rides the `utils/compat.install_compile_listener` chokepoint
(jax-version drift owned there, not here): every completed
trace/lower/backend-compile interval lands in `record_interval`, every
persistent-cache hit/miss point event in `record_cache_event`. Backend
compiles are the ones that cost real wall time, so they are what the
per-program table, the `compile.backend` telemetry spans, and the
steady-state gauge count.

Steady state: `mark_steady()` draws the line after an app's warmup (and
after any deliberately-compiled probe/heartbeat programs). Every backend
compile after the mark is a RECOMPILE — `steady_state()` returns the
count, `emit_gauges()` banks it as the `compiles.steady_state` gauge,
and the regress gate treats `compiles.*` gauges as lower-is-better with
a meaningful zero (telemetry/regress.py), so a committed baseline of 0
makes any steady-state recompile a gated regression.

jax is imported only inside `install()`; everything else is stdlib, so
the read-side CLI can import this module's constants freely.
"""

from __future__ import annotations

import threading
import time

from rocm_mpi_tpu.telemetry import events
from rocm_mpi_tpu.telemetry.spans import span_record

_LOCK = threading.Lock()
_MODE: str | None = None
_PROGRAMS: dict[str, dict] = {}   # name -> {"count", "wall_s", "steady"}
_TOTALS = {"backend_compiles": 0, "cache_hits": 0, "cache_misses": 0}
_STEADY_MARKED = False
_STEADY_EVER = False
_STEADY_RECOMPILES = 0


def install() -> str | None:
    """Install the compile listener (idempotent; returns the mode,
    "named": per-program attribution). Safe
    to call whether or not telemetry collection is on: recording is a
    counter bump; the telemetry span is emitted only when enabled."""
    global _MODE
    if _MODE is not None:
        return _MODE
    from rocm_mpi_tpu.utils.compat import install_compile_listener

    _MODE = install_compile_listener(record_interval, record_cache_event)
    return _MODE


def record_interval(event: str, name: str | None, dur_s: float) -> None:
    """One completed compile-pipeline interval (the compat hook's
    callback; also the test seam — no jax needed to drive it)."""
    if not isinstance(event, str) or not event.endswith(
        "backend_compile_duration"
    ):
        return
    global _STEADY_RECOMPILES
    prog = name or "<unnamed>"
    with _LOCK:
        row = _PROGRAMS.setdefault(
            prog, {"count": 0, "wall_s": 0.0, "steady": 0}
        )
        row["count"] += 1
        row["wall_s"] += float(dur_s)
        _TOTALS["backend_compiles"] += 1
        steady = _STEADY_MARKED
        if steady:
            row["steady"] += 1
            _STEADY_RECOMPILES += 1
    if events.enabled():
        span_record(
            "compile.backend", time.time() - dur_s, dur_s,
            phase="compile", program=prog, steady=steady,
        )


def record_cache_event(event: str) -> None:
    if not isinstance(event, str):
        return
    with _LOCK:
        if event.endswith("/cache_hits"):
            _TOTALS["cache_hits"] += 1
        elif event.endswith("/cache_misses"):
            _TOTALS["cache_misses"] += 1


def mark_steady() -> None:
    """Open a steady-state window: every backend compile until
    `unmark_steady()` is a recompile the steady-state gauge (and the
    regress gate) counts. A weak-scaling ladder opens one window per
    rung's timed loop — each rung's warmup/mesh compiles are legitimate
    and happen OUTSIDE the windows; the count accumulates across them."""
    global _STEADY_MARKED, _STEADY_EVER
    with _LOCK:
        _STEADY_MARKED = True
        _STEADY_EVER = True


def unmark_steady() -> None:
    """Close the current steady-state window (rung boundary)."""
    global _STEADY_MARKED
    with _LOCK:
        _STEADY_MARKED = False


def steady_marked() -> bool:
    return _STEADY_MARKED


def steady_state() -> int:
    """Backend compiles since mark_steady() — the "recompiles after
    warmup" number; 0 is the healthy steady state."""
    return _STEADY_RECOMPILES


def snapshot() -> dict:
    """The full compile accounting (monitor/test surface)."""
    with _LOCK:
        return {
            "mode": _MODE,
            "programs": {k: dict(v) for k, v in _PROGRAMS.items()},
            "totals": dict(_TOTALS),
            "steady_marked": _STEADY_MARKED,
            "steady_ever_marked": _STEADY_EVER,
            "steady_recompiles": _STEADY_RECOMPILES,
        }


def emit_gauges() -> None:
    """Bank the compile accounting into the telemetry stream. Call at
    the end of the measured window, BEFORE any deliberately-compiled
    epilogue (phase probes): their compiles are paid-for tooling, not
    steady-state recompiles. `compiles.steady_state` is only emitted
    once mark_steady() ran — an unmarked run has no warmup line and a
    fake 0 would green-gate it."""
    if not events.enabled():
        return
    with _LOCK:
        total = _TOTALS["backend_compiles"]
        misses = _TOTALS["cache_misses"]
        ever_marked = _STEADY_EVER
        steady = _STEADY_RECOMPILES
        per_program = {k: v["count"] for k, v in _PROGRAMS.items()}
    if _MODE is None and not total and not misses:
        # No listener ever installed and nothing recorded: these zeros
        # would be fabrication, not measurement — a recompile storm in
        # such a run would read as a green steady_state baseline.
        return
    events.gauge("compiles.total", total)
    events.gauge("compiles.cache_misses", misses)
    if ever_marked:
        events.gauge("compiles.steady_state", steady)
    for prog, count in sorted(per_program.items()):
        events.annotate("compiles.program", program=prog, count=count)


def reset() -> None:
    """Test isolation: drop the accounting (the installed hook stays —
    uninstalling a process-wide tap mid-run would lose compiles)."""
    global _STEADY_MARKED, _STEADY_EVER, _STEADY_RECOMPILES
    with _LOCK:
        _PROGRAMS.clear()
        _TOTALS.update(backend_compiles=0, cache_hits=0, cache_misses=0)
        _STEADY_MARKED = False
        _STEADY_EVER = False
        _STEADY_RECOMPILES = 0

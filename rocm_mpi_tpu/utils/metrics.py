"""Walltime timers and the performance metrics (D5, SURVEY.md §5.5).

The reference's one metric is effective memory throughput
    T_eff = A_eff / wtime_it,  A_eff = (2+1)/1e9 · nx·ny · sizeof(dtype) GB
(read T + write T2 + read Cp = 3 whole-array passes), with
    wtime_it = wtime / (nt - warmup)
excluding 10 warmup iterations (/root/reference/scripts/diffusion_2D_perf.jl:55-58,
tic/toc at :48,53). The driver's headline metric Gpts/s = nx·ny/wtime_it/1e9
is the same measurement, hardware-agnostically normalized per grid point.

TPU note: `tic`/`toc` bracket device work with `jax.block_until_ready` —
the analog of the reference's `wait(signal)` sync before `toc` — because
JAX dispatch is async: a timing without it measures the enqueue.

Since the telemetry subsystem (rocm_mpi_tpu/telemetry/, docs/TELEMETRY.md)
this module is the compatibility surface: the structured-event API
(`record_event`/`events`/`clear_events`) is a thin shim over
`telemetry.events`, and a *labeled* Timer feeds its interval into the
telemetry stream. New code should prefer `telemetry.span(...)` directly —
bare `tic()`/`toc()` remains supported for the models' measurement loops
but is deprecated in apps, where raw timing is also lint-gated (graftlint
GL06 flags `time.perf_counter()`/`time.time()` outside this module and
telemetry/).
"""

from __future__ import annotations

import math
import time

import jax

from rocm_mpi_tpu.telemetry import events as _tel


def force(x):
    """Wait for the device work behind `x` (`jax.block_until_ready`)."""
    return jax.block_until_ready(x)


class Timer:
    """tic/toc walltime timer (ImplicitGlobalGrid tic()/toc() analog),
    also usable as a context manager:

        with Timer() as timer:
            state = advance(state, n)   # sync yourself, or...
            timer.toc(state)            # ...toc explicitly with sync args
        wtime = timer.elapsed

    __exit__ calls toc() only when the body didn't — an explicit
    toc(*sync) inside the block keeps the device sync semantics and
    wins over the exit stamp. A `label` opens a `telemetry.span` of that
    name, with `attrs`, from tic to toc (a record in the telemetry
    stream and an annotation on the profiler's clock, when telemetry is
    enabled); unlabeled timers stay telemetry-silent, exactly as before.
    Use the `with` form where the timed work can raise: it closes the
    span error-flagged.
    """

    def __init__(self, label: str | None = None, **attrs):
        self._t0 = None
        self._span = None
        self.elapsed = None
        self.label = label
        self.attrs = attrs

    def _close_span(self, exc_type=None):
        if self._span is not None:
            self._span.__exit__(exc_type, None, None)
            self._span = None

    def tic(self, *sync):
        """Start timing. Pass device arrays to sync on first."""
        for x in sync:
            force(x)
        self.elapsed = None
        self._close_span()
        if self.label is not None:
            from rocm_mpi_tpu.telemetry.spans import span

            self._span = span(self.label, **self.attrs).__enter__()
        self._t0 = time.perf_counter()

    def toc(self, *sync) -> float:
        """Stop timing (after syncing on `sync`); returns elapsed seconds."""
        for x in sync:
            force(x)
        if self._t0 is None:
            raise RuntimeError("toc() before tic()")
        self.elapsed = time.perf_counter() - self._t0
        self._close_span()
        return self.elapsed

    def __enter__(self):
        self.tic()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self.elapsed is None and self._t0 is not None:
            if exc_type is None:
                self.toc()
            else:
                # A failing body still gets its interval recorded (no
                # sync — there may be nothing coherent to sync on): the
                # hours a run burned before the supervisor gave up must
                # show in the stream, error-flagged like a failed span.
                self.elapsed = time.perf_counter() - self._t0
                self._close_span(exc_type)
        return False


def wtime_per_it(wtime: float, nt: int, warmup: int = 10) -> float:
    """wtime_it = wtime/(nt - warmup) (perf.jl:56)."""
    if nt <= warmup:
        raise ValueError(f"nt={nt} must exceed warmup={warmup}")
    return wtime / (nt - warmup)


def a_eff_gb(shape, itemsize: int, n_passes: int = 3) -> float:
    """A_eff in GB: n_passes whole-array memory passes per step (perf.jl:55)."""
    return n_passes / 1e9 * math.prod(shape) * itemsize


def t_eff_gbs(shape, itemsize: int, wtime_it: float, n_passes: int = 3) -> float:
    """Effective memory throughput T_eff [GB/s] (perf.jl:57)."""
    return a_eff_gb(shape, itemsize, n_passes) / wtime_it


def gpts_per_s(shape, wtime_it: float) -> float:
    """Grid points processed per second [Gpts/s] — the driver's metric."""
    return math.prod(shape) / wtime_it / 1e9


# ---------------------------------------------------------------------------
# Structured run events — a compatibility shim over telemetry.events.
#
# The PR-1 resilience layer introduced this API; the telemetry subsystem
# now owns the storage (versioned records, per-rank JSONL writers,
# RMT_EVENT_LOG legacy tee — rocm_mpi_tpu/telemetry/events.py). The
# RunEvent view below preserves every pre-telemetry caller (tests,
# supervisor post-mortems) while new fields — the satellite fixes —
# ride along: `t_mono` (monotonic, orders events within a rank; the old
# wall-only stamp couldn't) and `v` (the event-schema version the old
# lines lacked).
# ---------------------------------------------------------------------------

import dataclasses  # noqa: E402  (grouped with the shim it serves)
import json  # noqa: E402


@dataclasses.dataclass(frozen=True)
class RunEvent:
    """One structured resilience event (retry, restore, give-up...)."""

    kind: str            # e.g. "attempt-failed", "backoff", "restored"
    t: float             # wall time at emission (comparable across ranks)
    attempt: int | None = None
    step: int | None = None
    wait_s: float | None = None
    error: str | None = None
    t_mono: float | None = None  # monotonic stamp (ordering within a rank)
    v: int = _tel.SCHEMA_VERSION

    def to_json(self) -> str:
        return json.dumps(
            {k: v for k, v in dataclasses.asdict(self).items()
             if v is not None}
        )


def _as_run_event(rec: dict) -> RunEvent:
    return RunEvent(
        kind=rec["name"], t=rec["t"], attempt=rec.get("attempt"),
        step=rec.get("step"), wait_s=rec.get("wait_s"),
        error=rec.get("error"), t_mono=rec.get("t_mono"),
        v=rec.get("v", _tel.SCHEMA_VERSION),
    )


def record_event(kind: str, *, attempt=None, step=None, wait_s=None,
                 error=None) -> RunEvent:
    """Append a structured event (telemetry stream + RMT_EVENT_LOG tee)."""
    rec = _tel.record_event(kind, attempt=attempt, step=step,
                            wait_s=wait_s, error=error)
    return _as_run_event(rec)


def events(kind: str | None = None) -> list[RunEvent]:
    """The in-process event trail (optionally filtered by kind)."""
    return [
        _as_run_event(r)
        for r in _tel.records(kind="event", name=kind)
    ]


def clear_events() -> None:
    """Deprecated alias for `telemetry.clear_events()` — the one public
    reset for the event trail (events dropped, buffered spans/gauges and
    the trace-annotation dedup state preserved). The two spellings used
    to live side by side with the behavior defined only here; the
    telemetry side now owns it (the flight recorder's reset path goes
    through the same function), and this shim just forwards."""
    import warnings

    warnings.warn(
        "utils.metrics.clear_events() is deprecated; call "
        "telemetry.clear_events()",
        DeprecationWarning,
        stacklevel=2,
    )
    _tel.clear_events()

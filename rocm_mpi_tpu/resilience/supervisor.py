"""Supervised segmented runs: retry/backoff around checkpointed advance
(docs/RESILIENCE.md §1).

A long run's expected failure count is > 0 (preemption, a lost host,
checkpoint-IO errors). `run_supervised` is the process-level answer: it
resumes from the latest VALID checkpoint instead of restarting from
step 0:

    state = run_supervised(advance, init_state, nt, directory, every)

is `utils/checkpoint.run_segmented` wrapped in a supervision loop:

  * a crash (any exception the policy classifies as retryable — backend
    errors, injected faults, OOM-class runtime errors) re-resolves
    `latest_valid_step` — NOT merely latest: a crash mid-save leaves a
    torn checkpoint, which validation skips, falling back to the
    previous kept step;
  * the restart waits exponential-backoff long (base * factor**attempt,
    capped), exactly like the bench parent's child-retry policy;
  * attempts are bounded; exhaustion re-raises the last failure — a
    supervisor must never convert a persistent failure into silence;
  * every decision emits a structured telemetry event
    ("attempt-failed" / "backoff" / "restored" / "recovered" /
    "gave-up") — versioned, monotonic-stamped, written to the rank's
    telemetry stream when collection is on (docs/TELEMETRY.md), and
    still visible through the legacy `utils.metrics.events()` view —
    so the retry history is machine-readable next to the run's
    performance metrics.

The advance contract is unchanged (`advance(state, n) -> state`, traced
n) — supervision composes around the compiled program, never inside it.

Scope: this supervisor retries on the SAME topology — right when the
failure was transient (backend flap, IO hiccup). When the topology
itself died (watchdog-killed rank, preempted pod, vanished container),
retrying the same mesh can only fail again; that case belongs to the
launcher-level ELASTIC supervisor (resilience.elastic.run_elastic),
which shrinks to the largest valid sub-mesh and resumes from the latest
valid step through the v2 manifests' topology metadata
(docs/RESILIENCE.md "Elastic recovery").
"""

from __future__ import annotations

import time

from rocm_mpi_tpu import telemetry
from rocm_mpi_tpu.utils import checkpoint as ckpt


def default_retryable(exc: BaseException) -> bool:
    """Crash classification: retry runtime/backend/injected failures;
    never retry programming errors (TypeError, ValueError...) — those
    reproduce identically and must surface immediately."""
    from rocm_mpi_tpu.resilience.faults import InjectedCrash

    if isinstance(exc, InjectedCrash):
        return True
    if isinstance(exc, (KeyboardInterrupt, SystemExit)):
        return False
    # XlaRuntimeError subclasses RuntimeError in every jax this targets;
    # OSError covers checkpoint-IO failures (a write dropped mid-save).
    return isinstance(exc, (RuntimeError, OSError))


def run_supervised(
    advance,
    init_state,
    nt: int,
    directory,
    every: int,
    *,
    max_retries: int = 3,
    backoff_s: float = 0.5,
    backoff_factor: float = 2.0,
    backoff_max_s: float = 60.0,
    resume: bool = True,
    retryable=default_retryable,
    sleep=time.sleep,
    log=None,
):
    """Run `nt` steps of `advance` with checkpointing every `every` steps
    under crash supervision; returns the final state.

    `init_state` is BOTH the cold-start state and the restore template
    (shapes/dtypes/shardings) — the same dual role the apps' --resume
    path gives it. With resume=True an existing valid checkpoint in
    `directory` is continued even on the first attempt, so a re-invoked
    process (the watcher's retry, a preempted pod) supervises seamlessly
    into the same run.

    `max_retries` bounds RESTARTS (attempts = max_retries + 1);
    exhaustion re-raises the last exception after a "gave-up" event.
    `sleep` is injectable so tests assert the exponential schedule
    without waiting it out.
    """
    if max_retries < 0:
        raise ValueError(f"max_retries must be >= 0, got {max_retries}")
    log = log or (lambda *_: None)

    import jax
    import jax.numpy as jnp

    # `init_state` itself is NEVER handed to the advance: the framework's
    # advances donate their state argument, so a cold restart after a
    # pre-first-checkpoint crash would otherwise feed already-donated
    # buffers back in and die on a (non-retryable) deleted-buffer error —
    # exactly when supervision matters most. Each cold start gets a fresh
    # copy; the pristine original stays valid as the restore template
    # (shapes/dtypes/shardings survive regardless).
    def cold_state():
        return jax.tree_util.tree_map(jnp.copy, init_state)

    def resolve_start():
        """(start_step, state) from the latest VALID checkpoint."""
        start = ckpt.latest_valid_step(directory, log=log)
        if start is None:
            return 0, cold_state()
        state = ckpt.restore_state(directory, start, init_state)
        telemetry.record_event("restored", step=start)
        log(f"supervisor: restored step {start} from {directory}")
        return start, state

    attempt = 0
    recovered = False
    while True:
        try:
            if resume or attempt > 0:
                start, state = resolve_start()
            else:
                start, state = 0, cold_state()
            if start >= nt:
                log(f"supervisor: checkpoint already at step {start} >= "
                    f"nt={nt}; nothing to run")
                final = state
            else:
                final = ckpt.run_segmented(
                    advance, state, nt, directory, every, start_step=start
                )
            if recovered:
                telemetry.record_event("recovered", attempt=attempt, step=nt)
            return final
        except BaseException as exc:  # noqa: BLE001 — classified below
            if not retryable(exc):
                raise
            err = f"{type(exc).__name__}: {exc}"
            telemetry.record_event(
                "attempt-failed", attempt=attempt, error=err
            )
            log(f"supervisor: attempt {attempt} failed — {err}")
            if attempt >= max_retries:
                telemetry.record_event(
                    "gave-up", attempt=attempt, error=err
                )
                log(f"supervisor: giving up after {attempt + 1} attempts")
                raise
            wait = min(backoff_s * backoff_factor**attempt, backoff_max_s)
            telemetry.record_event("backoff", attempt=attempt, wait_s=wait)
            log(f"supervisor: retrying in {wait:.2f}s")
            sleep(wait)
            attempt += 1
            recovered = True

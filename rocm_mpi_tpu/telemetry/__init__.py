"""Structured observability for the framework (docs/TELEMETRY.md).

The write side (this package's hot half) is stdlib-only and gated on one
bool so instrumented code costs nothing when telemetry is off:

    from rocm_mpi_tpu import telemetry

    telemetry.configure(directory="out/telemetry", rank=jax.process_index())
    with telemetry.span("step_window", phase="step", steps=50) as sp:
        T = advance(T, Cp, 50)
        sp.sync(T)                      # jax.block_until_ready
    telemetry.gauge("run.gpts", r.gpts)
    telemetry.record_event("restored", step=120)   # resilience kinds

Every rank appends to its own `telemetry-rank{k}.jsonl` (versioned
schema: telemetry.events). The read side merges them:

    python -m rocm_mpi_tpu.telemetry summarize DIR        # + Chrome trace
    python -m rocm_mpi_tpu.telemetry regress S --baseline B

Layer map: spans/events collect (write side); aggregate merges and
attributes (halo / interior / checkpoint / step, stragglers); trace
exports to Perfetto; regress gates PRs on committed baselines; probes
(jax-needing, imported lazily) measure phase attribution for fused step
programs that expose no seams at runtime.

The runtime health plane rides on top (docs/TELEMETRY.md "Health
plane"): flight (write side — per-rank flight recorder, heartbeat
sidecars, SIGUSR2 post-mortems), health (read side — sidecar tailing,
the progress-aware stall verdict, monitor/OpenMetrics), compiles
(per-program compile + recompile accounting through utils/compat):

    python -m rocm_mpi_tpu.telemetry monitor DIR
    python -m rocm_mpi_tpu.telemetry export-openmetrics DIR
"""

from rocm_mpi_tpu.telemetry.events import (
    SCHEMA_VERSION,
    annotate,
    clear,
    clear_events,
    configure,
    counter,
    enabled,
    gauge,
    rank,
    record_event,
    records,
    stream_path,
)
from rocm_mpi_tpu.telemetry.spans import span, span_record

__all__ = [
    "SCHEMA_VERSION",
    "annotate",
    "clear",
    "clear_events",
    "configure",
    "counter",
    "enabled",
    "gauge",
    "rank",
    "record_event",
    "records",
    "span",
    "span_record",
    "stream_path",
]

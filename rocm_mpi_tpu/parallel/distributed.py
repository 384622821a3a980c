"""Multi-host process wiring (D9) — the launch-layer analog.

Reference: processes are created by Slurm (`srun -n N --mpi=pmix`), wired
into MPI_COMM_WORLD by PMIx, and each rank binds one GPU via the node-local
communicator split (/root/reference/README.md:18,
scripts/rocmaware_test_selectdevice.jl:7-9; SURVEY.md §2.2 D9).

TPU-native: one process per host, `jax.distributed.initialize()` discovers
the pod slice (coordinator/process env comes from the TPU runtime or the
launcher), and every local chip is bound automatically — there is no manual
device selection to do. Cross-host collectives ride DCN, intra-slice ride
ICI. `scripts/run.sh` sets RMT_DISTRIBUTED=1 on multi-host launches, the
runme.sh analog.
"""

from __future__ import annotations

import os

_initialized = False


def process_id() -> int:
    """This process's rank under the launcher contract, WITHOUT forcing
    backend/cluster init: RMT_PROCESS_ID when the launcher set it, else
    jax.process_index() if a backend is already up, else 0. The
    resilience layer's rank-scoped fault clauses key off this."""
    raw = os.environ.get("RMT_PROCESS_ID")
    if raw is not None:
        try:
            return int(raw)
        except ValueError:
            pass
    import sys

    jax = sys.modules.get("jax")
    if jax is not None:
        try:
            return jax.process_index()
        except Exception:  # noqa: BLE001 — backend may not be up yet
            pass
    return 0


def maybe_initialize_distributed() -> bool:
    """Call jax.distributed.initialize() when a multi-host launch is
    requested (RMT_DISTRIBUTED=1, or explicit JAX coordinator env).

    Idempotent; returns True when running in (or just joined) a multi-host
    setup. Single-host runs are a no-op — the reference's single-node case.

    Process wiring comes from either (a) JAX's cluster auto-detection
    (TPU pod runtime, Slurm, Open MPI — the srun/PMIx analog), or (b) the
    framework's own explicit launcher contract, mirroring how PMIx hands
    each rank its identity (README.md:18):

        RMT_COORDINATOR = host:port of process 0's coordinator service
        RMT_NUM_PROCS   = total process count
        RMT_PROCESS_ID  = this process's rank

    All three must be set together; scripts/run.sh exports them on
    multi-host launches.
    """
    global _initialized
    import jax

    if _initialized:
        return True
    env = os.environ
    want = env.get("RMT_DISTRIBUTED") == "1" or (
        "JAX_COORDINATOR_ADDRESS" in env or "RMT_COORDINATOR" in env
    )
    if not want:
        return False
    def int_env(name: str) -> int:
        try:
            val = env[name]
        except KeyError:
            raise RuntimeError(
                f"RMT_COORDINATOR requires {name} to be set too"
            ) from None
        try:
            return int(val)
        except ValueError:
            raise RuntimeError(
                f"{name} must be an integer, got {val!r}"
            ) from None

    kwargs = {}
    if "RMT_COORDINATOR" in env:
        kwargs = dict(
            coordinator_address=env["RMT_COORDINATOR"],
            num_processes=int_env("RMT_NUM_PROCS"),
            process_id=int_env("RMT_PROCESS_ID"),
        )
        if "RMT_INIT_TIMEOUT_S" in env:
            kwargs["initialization_timeout"] = int_env("RMT_INIT_TIMEOUT_S")
    # Resilience drill site: a delay-rank fault here simulates the slow/
    # stalled joiner the launcher's heartbeat reporting must surface.
    from rocm_mpi_tpu.resilience import faults

    faults.fault_point("init")
    jax.distributed.initialize(**kwargs)
    _initialized = True
    return True

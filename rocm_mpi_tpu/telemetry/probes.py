"""Phase-attribution probes: measure halo / interior / checkpoint cost
when the hot loop itself exposes no runtime seams.

The framework's whole design keeps the step fused — halo ppermutes and
interior compute live inside ONE compiled program precisely so XLA can
overlap them (parallel/overlap.py), and the time loop never leaves the
device (models/*.py fori_loop). That is the right execution schedule and
the wrong measurement surface: there is no host-visible boundary to
span. The standard answer (both tuning surveys the ROADMAP cites —
arxiv 2406.08923, 2404.04441 — lean on it) is differential probing: run
each phase as its OWN compiled program over the same state and time it
under a span. The probe programs are built from the very building blocks
the fused step composes (exchange_halo, the padded stencil update,
save/restore), so the attribution measures the real kernels, not a
model of them.

Caveat stamped into every probe span (`attrs["probe"] = True`): probe
phases run serially, so their sum exceeds a fused step that overlaps
them — the summary's `step` phase is the ground truth for total time;
probes attribute, they do not re-measure.

This module needs jax; the telemetry package imports it lazily so the
stdlib-only read side (aggregate/trace/regress CLI) stays jax-free.
"""

from __future__ import annotations

from rocm_mpi_tpu.telemetry import events
from rocm_mpi_tpu.telemetry.spans import span


def run_diffusion_phase_probes(model, iters: int = 10,
                               checkpoint_dir=None,
                               driver: str | None = None) -> None:
    """Measure halo / interior (and optionally checkpoint) phases for a
    HeatDiffusion model, emitting one span per phase.

    `iters` iterations run inside one jitted fori_loop per probe (one
    dispatch, no per-iteration host round-trips), after a warmup call
    that eats the compile. With `checkpoint_dir`, one save/restore cycle
    runs through utils.checkpoint — whose own spans provide the
    checkpoint attribution (every process must call this on multi-host
    runs: orbax saves are collective). `driver` stamps the loop form the
    probed run used (scan or deep) on every probe span: phase
    attributions banked from a scan-driver run and a deep-sweep run are
    different measurements and must say so.
    """
    if not events.enabled():
        return
    import functools

    import jax
    import jax.numpy as jnp
    from jax import lax

    from rocm_mpi_tpu.ops.diffusion import step_fused_padded
    from rocm_mpi_tpu.parallel.halo import exchange_halo, exchange_nbytes
    from rocm_mpi_tpu.utils.compat import shard_map

    cfg, grid = model.config, model.grid
    T, Cp = model.init_state()
    dt = cfg.jax_dtype(cfg.dt)
    core = tuple(slice(1, -1) for _ in range(grid.ndim))
    n_local_devices = sum(
        1 for d in grid.mesh.devices.flat
        if d.process_index == jax.process_index()
    )
    per_exchange = exchange_nbytes(
        grid.local_shape, jnp.dtype(cfg.jax_dtype).itemsize
    )

    @functools.partial(jax.jit, static_argnums=1)
    def halo_probe(x, n):
        def local(xl):
            def body(_, cur):
                return exchange_halo(cur, grid)[core]

            return lax.fori_loop(0, n, body, xl)

        return shard_map(
            local, mesh=grid.mesh, in_specs=(grid.spec,),
            out_specs=grid.spec, check_vma=False,
        )(x)

    @functools.partial(jax.jit, static_argnums=2)
    def interior_probe(x, c, n):
        def local(xl, cl):
            def body(_, cur):
                # Zero-padded block: the same stencil update the fused
                # step applies, with no communication to hide behind.
                return step_fused_padded(
                    jnp.pad(cur, 1), cl, cfg.lam, dt, cfg.spacing
                )

            return lax.fori_loop(0, n, body, xl)

        return shard_map(
            local, mesh=grid.mesh, in_specs=(grid.spec, grid.spec),
            out_specs=grid.spec, check_vma=False,
        )(x, c)

    from rocm_mpi_tpu.utils.metrics import force

    # Warm with the SAME static iteration count the span will use:
    # `n` is a static argument, so a warmup at a different n compiles a
    # different program and the span would time the compile, not the
    # kernels — poisoning every baseline banked from the run.
    stamp = {} if driver is None else {"driver": driver}
    force(halo_probe(T, iters))
    with span(
        "halo.probe", phase="halo", probe=True, iters=iters,
        bytes=per_exchange * n_local_devices * iters, **stamp,
    ) as sp:
        sp.sync(halo_probe(T, iters))

    force(interior_probe(T, Cp, iters))
    with span(
        "interior.probe", phase="interior", probe=True, iters=iters,
        **stamp,
    ) as sp:
        sp.sync(interior_probe(T, Cp, iters))

    if checkpoint_dir is not None:
        from rocm_mpi_tpu.utils import checkpoint as ckpt

        try:
            # The spans come from checkpoint.py's own instrumentation;
            # the probe just drives one full save/validate/restore cycle.
            ckpt.save_state(checkpoint_dir, 0, (T,))
            ckpt.restore_state(checkpoint_dir, 0, (T,))
        except Exception as e:  # noqa: BLE001 — a probe must not kill the run
            events.record_event("probe-failed", error=f"checkpoint: {e!r}")


def make_halo_heartbeat(model):
    """Build the per-window halo heartbeat for the health plane: one
    compiled single-exchange program over `model`'s grid, returned as
    `beat(x) -> x` which runs the exchange under a
    `halo.heartbeat` span (phase=halo, probe=True, real wire bytes).

    Purpose (docs/TELEMETRY.md "Health plane"): the fused windowed run
    gives the flight recorder nothing halo-shaped at runtime — the
    exchanges live inside the compiled window. One real cross-rank
    exchange per window boundary is a live probe of the collective
    fabric: its span feeds the flight ring (so a rank wedged at a
    boundary reads "last phase: halo", which is what it is blocked on),
    its latency lands in the halo phase attribution marked probe:true,
    and its cost is one exchange per WINDOW, not per step. Compile the
    returned callable once (call it during warmup, before
    compiles.mark_steady) — it is jitted and reused.
    """
    import jax
    import jax.numpy as jnp

    from rocm_mpi_tpu.parallel.halo import exchange_halo, exchange_nbytes
    from rocm_mpi_tpu.utils.compat import shard_map

    grid = model.grid
    cfg = model.config
    core = tuple(slice(1, -1) for _ in range(grid.ndim))
    n_local_devices = sum(
        1 for d in grid.mesh.devices.flat
        if d.process_index == jax.process_index()
    )
    nbytes = exchange_nbytes(
        grid.local_shape, jnp.dtype(cfg.jax_dtype).itemsize
    ) * n_local_devices

    @jax.jit
    def one_exchange(x):
        def local(xl):
            return exchange_halo(xl, grid)[core]

        return shard_map(
            local, mesh=grid.mesh, in_specs=(grid.spec,),
            out_specs=grid.spec, check_vma=False,
        )(x)

    def beat(x):
        with span("halo.heartbeat", phase="halo", probe=True,
                  bytes=nbytes) as sp:
            return sp.sync(one_exchange(x))

    return beat

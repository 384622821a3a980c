"""Strip-assembly overhead of the hide program (VERDICT r3 #6).

The hide variant's per-shard work is the boundary-slab/interior
decomposition of parallel.overlap.make_overlap_step: per step it launches
one region kernel per slab plus the interior and concatenates the pieces —
machinery whose *benefit* (hiding the exchange) needs ≥2 chips, but whose
*cost* does not: on one chip the same decomposition can be timed against
the monolithic whole-shard kernel the perf variant runs.

A/B protocol (within one process, baseline first and last):
for each shard size × b_width, time
  mono  — the per-step Cm-masked whole-shard program
          (ops.pallas_kernels.masked_step, what perf runs unsharded), and
  strip — make_overlap_step on a 1-device grid with the same fused_step_cm
          region kernel and the same Cm contract (exactly the multi-device
          hide program's per-shard work; the 1-device ppermute is a no-op,
          so the difference IS the strip machinery: slab slicing, extra
          kernel launches, concatenation).
Overhead % = strip/mono − 1. This is the data behind the b_width default
(config.py's (32,4), the reference's knob, hide.jl:42 — untimed until now).

Run on the chip:  python scripts/bench_strip_overhead.py [timed_steps]
Output committed as docs/strip_overhead_r4.txt.
"""

import functools
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

TIMED_DEFAULT = 65_536
WARMUP = 4_096


from rocm_mpi_tpu.utils.backend import (
    enable_persistent_cache,
    require_accelerator,
)  # noqa: E402


def main(argv=None) -> int:
    argv = list(argv) if argv else []
    # Queue runs pass --require-accelerator so a mid-queue CPU fallback
    # exits nonzero (→ INCOMPLETE artifact, retried) instead of promoting
    # interpret-mode numbers as the completed chip measurement.
    require_accel = "--require-accelerator" in argv
    argv = [a for a in argv if a != "--require-accelerator"]
    timed = int(argv[0]) if argv else TIMED_DEFAULT
    enable_persistent_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from rocm_mpi_tpu.utils.compat import shard_map

    from rocm_mpi_tpu.config import DiffusionConfig
    from rocm_mpi_tpu.models import HeatDiffusion
    from rocm_mpi_tpu.ops.pallas_kernels import fused_step_cm, masked_step
    from rocm_mpi_tpu.parallel.overlap import (
        effective_b_width,
        make_overlap_step,
    )
    from rocm_mpi_tpu.utils import metrics

    if require_accel:
        require_accelerator("bench_strip_overhead.py")
    dev = jax.devices()[0]
    on_cpu = dev.platform == "cpu"
    if on_cpu:
        timed = min(timed, 64)
        print("NOTE: no accelerator — interpret-mode mechanics run, "
              "overhead numbers are NOT meaningful", flush=True)
    print(f"device: {dev} | f32 | warmup {WARMUP if not on_cpu else 8} "
          f"| timed {timed} steps/case", flush=True)
    warmup = WARMUP if not on_cpu else 8

    shard_sizes = [64, 128, 252, 504]
    b_widths = [(32, 4), (8, 8), (16, 16), (32, 32), (4, 4)]

    print(f"{'shard':>6} {'b_width':>9} {'mono µs':>9} {'strip µs':>9} "
          f"{'overhead':>9}")
    for n in shard_sizes:
        cfg = DiffusionConfig(
            global_shape=(n, n), lengths=(10.0, 10.0), nt=timed + warmup,
            warmup=warmup, dtype="f32", dims=(1, 1),
        )
        model = HeatDiffusion(cfg)
        grid = model.grid
        T0, Cp = model.init_state()
        dt = cfg.jax_dtype(cfg.dt)
        prep = model._cm_prepare()

        def time_advance(step_local):
            @functools.partial(jax.jit, donate_argnums=0)
            def advance(T, Cp, k):
                Cm = prep(Cp, cfg.lam, dt)
                body = lambda _, t: shard_map(
                    step_local, mesh=grid.mesh,
                    in_specs=(grid.spec, grid.spec), out_specs=grid.spec,
                    check_vma=False,
                )(t, Cm)
                return lax.fori_loop(0, k, body, T)

            T = advance(jnp.copy(T0), Cp, warmup)
            timer = metrics.Timer()
            timer.tic(T)
            T = advance(T, Cp, timed)
            w = timer.toc(T)
            return w / timed, np.asarray(T)

        # mono: the whole-shard Cm-masked kernel (the perf program).
        mono_t, mono_out = time_advance(
            lambda t, cm: masked_step(t, cm, cfg.spacing)
        )
        for bw in b_widths:
            local = make_overlap_step(
                grid,
                lambda tp, cm, lam, dt_, sp: fused_step_cm(tp, cm, sp),
                bw,
                mask_boundary=False,
            )
            strip_t, strip_out = time_advance(
                lambda t, cm: local(t, cm, cfg.lam, dt, cfg.spacing)
            )
            # Same trajectory: the strip program must be numerically
            # identical to the monolithic one (1-device ghosts are zeros
            # either way; Cm zeros hold the edge) — otherwise the timing
            # compares different programs.
            np.testing.assert_allclose(
                strip_out, mono_out, rtol=2e-6, atol=1e-7,
                err_msg=f"strip != mono at {n}² b_width={bw}",
            )
            eff = effective_b_width(grid.local_shape, bw)
            print(
                f"{n:6d} {str(eff):>9} {mono_t * 1e6:9.3f} "
                f"{strip_t * 1e6:9.3f} {strip_t / mono_t - 1.0:9.1%}",
                flush=True,
            )
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

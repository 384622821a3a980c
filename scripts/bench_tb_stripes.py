"""Stripe-shape sweep for the temporal-blocked HBM kernel (run_hbm_blocked).

The production configuration (_TB_TM=16 stripe rows, _TB_G=8 ghost rows,
k<=8 steps/sweep) re-reads (tm+2g)/tm = 2x the field per sweep and pays the
same redundancy in VPU work. A taller stripe cuts both: tm=32 reads 1.5x
and computes 1.5x. This script times candidate (tm, g, k) on the chip at
the reference's 12288² f32 geometry, within one process (run-to-run
variance cancels; baseline measured first and last), after a compiled
correctness check at 768² against the production configuration.

    python scripts/bench_tb_stripes.py [timed_steps]

The winner gets productized as the module constants in ops/pallas_kernels.py
with the measured numbers in PERF.md.
"""

import functools
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from rocm_mpi_tpu.ops.pallas_kernels import _make_tb_sweep, edge_masked_cm
from rocm_mpi_tpu.utils import metrics
from rocm_mpi_tpu.utils.backend import enable_persistent_cache, require_accelerator

N = 12288
CHECK_N = 768
LAM, CP0 = 1.0, 1.0

# (tm, g, k): stripe rows, ghost rows (= max k), steps per sweep.
CASES = [
    (16, 8, 8),   # production baseline
    (24, 8, 8),   # 1.67x redundancy
    (32, 8, 8),   # 1.5x redundancy
    (48, 8, 8),   # 1.33x — likely past the Mosaic/VMEM boundary
    (32, 16, 16),  # deeper sweeps: 2x redundancy but half the sweeps
]


def make_advance(T0, tm, g, k, inv_d2):
    sweep = _make_tb_sweep(T0, inv_d2, k, g, tm, interpret=False)

    @functools.partial(jax.jit, donate_argnums=0)
    def advance(T, Cm, n_sweeps):
        return lax.fori_loop(0, n_sweeps, lambda _, x: sweep(x, Cm), T)

    return advance


def state(n, key=0):
    spacing = 10.0 / n
    inv = 1.0 / (spacing * spacing)
    T0 = jax.random.uniform(jax.random.PRNGKey(key), (n, n), jnp.float32)
    Cp = jnp.full((n, n), CP0, jnp.float32)
    dt = spacing * spacing * CP0 / LAM / 4.1
    return T0, edge_masked_cm(T0, Cp, LAM, dt), (inv, inv)


def main():
    enable_persistent_cache()
    timed = int(sys.argv[1]) if len(sys.argv) > 1 else 1600
    require_accelerator("bench_tb_stripes.py")
    dev = jax.devices()[0]
    print(f"device: {dev} | {N}² f32 | timed {timed} steps")

    # Correctness referee at CHECK_N: production config, 32 steps.
    Tc, Cmc, invc = state(CHECK_N)
    ref = np.asarray(make_advance(Tc, 16, 8, 8, invc)(
        jnp.copy(Tc), Cmc, 32 // 8))

    T0, Cm, inv_d2 = state(N)
    order = CASES + [CASES[0]]
    advances = {}  # one compile + one referee check per case; repeats reuse
    for i, (tm, g, k) in enumerate(order):
        label = f"tm={tm} g={g} k={k}"
        try:
            if (tm, g, k) not in advances:
                chk = make_advance(Tc, tm, g, k, invc)
                out = np.asarray(chk(jnp.copy(Tc), Cmc, 32 // k))
                np.testing.assert_allclose(out, ref, rtol=2e-6, atol=1e-7)
                advances[(tm, g, k)] = make_advance(T0, tm, g, k, inv_d2)
            adv = advances[(tm, g, k)]
            nsw = timed // k
            T = adv(jnp.copy(T0), Cm, max(1, 16 // k))  # warmup/compile
            timer = metrics.Timer()
            timer.tic(T)
            T = adv(T, Cm, nsw)
            w = timer.toc(T)
            us = w / (nsw * k) * 1e6
            gpts = N * N / (w / (nsw * k)) / 1e9
            eq_gbs = 3 * N * N * 4 / (w / (nsw * k)) / 1e9
            print(f"[{i}] {label:18s} {us:9.3f} us/step  {gpts:7.2f} Gpts/s  "
                  f"T_eff(equiv)={eq_gbs:7.1f} GB/s")
        except Exception as e:  # compile/VMEM failures are data, not crashes
            lines = [ln for ln in str(e).splitlines() if ln.strip()]
            msg = lines[0][:120] if lines else type(e).__name__
            print(f"[{i}] {label:18s} FAILED: {msg}")


if __name__ == "__main__":
    main()

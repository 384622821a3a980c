"""Kernel-form A/B for the VMEM-resident multi-step kernel (the scored path).

Times candidate bodies of ops.pallas_kernels._multi_step_kernel at the
benchmark geometry (252² f32, chunk=256) within ONE process, so run-to-run
variance cancels and the comparison is within one run. The baseline form
is measured first AND last to expose drift.

Candidates:

  ac       — the production A/c form: T' = A∘T + Σ_ax c_ax∘(roll pair),
             prologue-hoisted coefficients (ops/pallas_kernels.py).
  eqc      — equal-spacing specialization (dx == dy, true of the benchmark
             geometry): the per-axis coefficients collapse to ONE array c,
             T' = A∘T + c∘(r₋x + r₊x + r₋y + r₊y) — one fewer VPU multiply
             per step.
  pad_ac   — the ac form on a 256²-padded layout: every vreg tile is full
             and the ±1 rolls are aligned power-of-two shifts. The pad ring
             carries Cm = 0, so pad cells never update and the interior is
             bit-identical to the 252² program (roll wraparound only ever
             reaches Cm==0 cells — same argument as the production kernel's
             Dirichlet ring).
  pad_eqc  — both.
  conly    — eqc minus the A array: T' = T + c∘(s − 2·ndim·T). Same op
             count as eqc at one fewer VMEM operand read per step (T and c
             instead of T, A, c); Dirichlet hold: c==0 ⇒ T'==T bitwise.
  pad_conly — conly on the 256²-padded layout.

Each candidate is cross-checked against the production form (256 steps,
allclose) before timing. Run on the chip:

    python scripts/bench_kernel_forms.py [timed_steps]

Output appended to stdout; the winning form gets productized in
ops/pallas_kernels.py with the measured numbers in its docstring.
"""

import functools
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from rocm_mpi_tpu.utils.compat import pallas as pl
from rocm_mpi_tpu.utils.compat import pallas_tpu as pltpu

from rocm_mpi_tpu.ops.pallas_kernels import edge_masked_cm
from rocm_mpi_tpu.utils import metrics
from rocm_mpi_tpu.utils.backend import enable_persistent_cache, require_accelerator

N = 252
PAD = 256
CHUNK = 256
WARMUP = 32_768
LAM, CP0 = 1.0, 1.0


def _body_ac(T, cs, A):
    acc = A * T
    for ax in range(T.ndim):
        acc = acc + cs[ax] * (jnp.roll(T, -1, ax) + jnp.roll(T, 1, ax))
    return acc


def _body_eqc(T, c, A):
    s = None
    for ax in range(T.ndim):
        r = jnp.roll(T, -1, ax) + jnp.roll(T, 1, ax)
        s = r if s is None else s + r
    return A * T + c * s


def _body_conly(T, c, nax):
    s = None
    for ax in range(T.ndim):
        r = jnp.roll(T, -1, ax) + jnp.roll(T, 1, ax)
        s = r if s is None else s + r
    return T + c * (s - (2.0 * nax) * T)


def _kernel(T_ref, Cm_ref, out_ref, *, inv_d2, form):
    Cm = Cm_ref[:]
    if form == "ac":
        cs = [Cm * inv for inv in inv_d2]
        A = 1.0 - 2.0 * functools.reduce(lambda a, b: a + b, cs)
        body = lambda _, T: _body_ac(T, cs, A)
    elif form == "eqc":
        assert all(inv == inv_d2[0] for inv in inv_d2)
        c = Cm * inv_d2[0]
        A = 1.0 - 2.0 * len(inv_d2) * c
        body = lambda _, T: _body_eqc(T, c, A)
    else:  # conly
        assert all(inv == inv_d2[0] for inv in inv_d2)
        c = Cm * inv_d2[0]
        body = lambda _, T: _body_conly(T, c, len(inv_d2))
    out_ref[:] = lax.fori_loop(0, CHUNK, body, T_ref[:], unroll=True)


def make_advance(shape, inv_d2, form):
    call = pl.pallas_call(
        functools.partial(_kernel, inv_d2=inv_d2, form=form),
        out_shape=jax.ShapeDtypeStruct(shape, jnp.float32),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
    )

    @functools.partial(jax.jit, donate_argnums=0)
    def advance(T, Cm, n):
        return lax.fori_loop(0, n // CHUNK, lambda _, x: call(x, Cm), T)

    return advance


def main():
    enable_persistent_cache()
    timed = int(sys.argv[1]) if len(sys.argv) > 1 else 8_388_608
    timed -= timed % CHUNK
    require_accelerator("bench_kernel_forms.py")
    dev = jax.devices()[0]
    print(f"device: {dev} | {N}² f32 chunk={CHUNK} | warmup {WARMUP} | "
          f"timed {timed}")

    spacing = 10.0 / N
    inv = 1.0 / (spacing * spacing)
    key = jax.random.PRNGKey(0)
    T0 = jax.random.uniform(key, (N, N), jnp.float32)
    Cp = jnp.full((N, N), CP0, jnp.float32)
    # dt small enough to stay stable over millions of steps
    dt = spacing * spacing * CP0 / LAM / 4.1
    Cm = edge_masked_cm(T0, Cp, LAM, dt)

    pad = ((0, PAD - N), (0, PAD - N))
    T0p = jnp.pad(T0, pad)
    Cmp = jnp.pad(Cm, pad)

    cases = {
        "ac": ((N, N), (inv, inv), "ac", T0, Cm, None),
        "eqc": ((N, N), (inv, inv), "eqc", T0, Cm, None),
        "conly": ((N, N), (inv, inv), "conly", T0, Cm, None),
        "pad_ac": ((PAD, PAD), (inv, inv), "ac", T0p, Cmp, (N, N)),
        "pad_eqc": ((PAD, PAD), (inv, inv), "eqc", T0p, Cmp, (N, N)),
        "pad_conly": ((PAD, PAD), (inv, inv), "conly", T0p, Cmp, (N, N)),
    }

    order = ["ac", "eqc", "conly", "pad_ac", "pad_eqc", "pad_conly", "ac"]
    advances = {}  # one compile per case; the repeat reuses it
    ref = None
    results = {}
    for i, name in enumerate(order):
        shape, inv_d2, form, T_init, Cm_case, crop = cases[name]
        if name not in advances:
            advances[name] = make_advance(shape, inv_d2, form)
        adv = advances[name]
        out = np.asarray(adv(jnp.copy(T_init), Cm_case, CHUNK))
        if crop:
            out = out[: crop[0], : crop[1]]
        if ref is None:
            ref = out  # first 'ac' run doubles as the correctness referee
        np.testing.assert_allclose(out, ref, rtol=1e-6, atol=1e-7,
                                   err_msg=f"form {name} diverges")
        T = adv(jnp.copy(T_init), Cm_case, WARMUP)
        timer = metrics.Timer()
        timer.tic(T)
        T = adv(T, Cm_case, timed)
        w = timer.toc(T)
        ns = w / timed * 1e9
        gpts = N * N / (w / timed) / 1e9
        tag = f"{name}[{i}]"
        results.setdefault(name, []).append(ns)
        print(f"{tag:12s} {ns:8.2f} ns/step   {gpts:8.2f} Gpts/s (252² pts)",
              flush=True)

    base = min(results["ac"])
    for name in order[1:-1]:
        ns = min(results[name])
        print(f"{name:10s} vs ac: {base / ns:.3f}x  ({base:.1f} -> {ns:.1f} ns)")


if __name__ == "__main__":
    main()

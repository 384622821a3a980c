"""bf16-vs-f32 error at run length (VERDICT r3 #4).

The bf16 fast path is a *labeled precision trade* the user opts into with
--dtype bf16: it halves per-step memory traffic. Round 3
documented its error at 4 steps only; this harness characterizes the
error-vs-steps curve out to the reference's full 1000-step run
(/root/reference/scripts/diffusion_2D_perf.jl:47 — nt=1000) at the
acceptance geometry (252²), so the trade's cost is known at the run length
the claim covers.

Protocol: advance the SAME per-step masked program (the schedule --dtype
selects, models.diffusion variant 'perf' → ops.pallas_kernels.masked_step)
in f32 and in bf16 from the same Gaussian IC; at log-spaced checkpoints
report the relative L2 error, the max pointwise error against the field
scale, and the peak-temperature drift (the max(T) decay invariant,
hide.jl:115). Measured finding: the error GROWS with run length — once
per-step field changes fall below bf16's 8-bit mantissa resolution, the
storage rounding accumulates as systematic drift (the bf16 peak decays
slower than f32's) rather than averaging out, so the trade is priced per
run length, not per step.

Run:  python scripts/bench_bf16_error.py            # on the chip
      JAX_PLATFORMS=cpu python scripts/bench_bf16_error.py --steps 128
                                                    # interpret-mode CPU
Output: one table row per checkpoint (committed as docs/bf16_error_r4.txt);
tests/test_bf16_error.py pins the 128-step bound from the same machinery.
"""

import argparse
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))


from rocm_mpi_tpu.utils.backend import (
    enable_persistent_cache,
    require_accelerator,
)  # noqa: E402


def error_curve(n=252, checkpoints=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512,
                                    1000), schedule="perf",
                vmem_chunk=None):
    """[(steps, rel_l2, rel_max, peak_f32, peak_bf16), ...] at n² — shared
    by the chip harness and the CPU test.

    schedule "perf": the per-step masked program (state rounds to storage
    dtype every step — the reference-parity schedule, advanced
    incrementally). schedule "vmem": the whole-loop-in-VMEM multi-step
    kernel, where bf16 is storage-only — f32 in-kernel compute, ONE
    rounding per chunk — so each checkpoint is a fresh run from the IC at
    that step count (chunk = gcd(steps, 256), or `vmem_chunk` to pin the
    rounding cadence — interpret-mode tracing cost grows superlinearly
    with the unroll, so the CPU test pins chunk=8; the cadence is part of
    what's measured, so incremental advance would distort it).
    """
    import jax
    import numpy as np

    from rocm_mpi_tpu.config import DiffusionConfig
    from rocm_mpi_tpu.models import HeatDiffusion

    if schedule not in ("perf", "vmem"):
        raise ValueError(f"schedule must be perf|vmem, got {schedule!r}")

    models = {}
    for dtype in ("f32", "bf16"):
        cfg = DiffusionConfig(
            global_shape=(n, n), lengths=(10.0, 10.0),
            nt=max(checkpoints), warmup=0, dtype=dtype, dims=(1, 1),
        )
        models[dtype] = HeatDiffusion(cfg)

    rows = []
    if schedule == "perf":
        states = {d: m.init_state() for d, m in models.items()}
        advances = {d: m.advance_fn("perf") for d, m in models.items()}
        done = 0
        for ck in checkpoints:
            delta = ck - done
            out = {}
            for dtype in ("f32", "bf16"):
                T, Cp = states[dtype]
                T = advances[dtype](T, Cp, delta)
                states[dtype] = (T, Cp)
                out[dtype] = T
            done = ck
            rows.append(_error_row(ck, out["f32"], out["bf16"]))
    else:
        for ck in checkpoints:
            chunk = None if vmem_chunk is None else min(vmem_chunk, ck)
            out = {}
            for dtype in ("f32", "bf16"):
                m = models[dtype]
                r = m.run_vmem_resident(nt=ck, warmup=0, chunk=chunk)
                out[dtype] = r.T
            rows.append(_error_row(ck, out["f32"], out["bf16"]))
    return rows


def _error_row(ck, a_dev, b_dev):
    import numpy as np

    a = np.asarray(a_dev, dtype=np.float64)
    b = np.asarray(b_dev, dtype=np.float64)
    rel_l2 = float(np.linalg.norm(b - a) / np.linalg.norm(a))
    rel_max = float(np.abs(b - a).max() / np.abs(a).max())
    return (ck, rel_l2, rel_max, float(a.max()), float(b.max()))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--n", type=int, default=252)
    p.add_argument("--steps", type=int, default=1000,
                   help="last checkpoint (smaller for interpret-mode runs)")
    p.add_argument("--schedule", default="perf", choices=["perf", "vmem"],
                   help="perf: per-step (rounds to storage dtype every "
                   "step); vmem: multi-step kernel (bf16 storage-only, "
                   "f32 compute, one rounding per chunk)")
    p.add_argument("--vmem-chunk", type=int, default=None,
                   help="pin the vmem schedule's rounding cadence "
                   "(interpret-mode runs need a small chunk — tracing "
                   "cost grows superlinearly with the unroll)")
    p.add_argument("--require-accelerator", action="store_true",
                   help="exit nonzero on the CPU fallback (queue runs: a "
                   "chip-labeled artifact must never hold interpret-mode "
                   "curves)")
    args = p.parse_args(argv)

    enable_persistent_cache()
    import jax

    if args.require_accelerator:
        require_accelerator("bench_bf16_error.py")
    plat = jax.devices()[0].platform
    print(f"device: {jax.devices()[0]} ({plat}); {args.n}² schedule="
          f"{args.schedule}, f32 vs bf16 from the same Gaussian IC",
          flush=True)
    if plat == "cpu":
        print("NOTE: interpret-mode Pallas (no accelerator) — error values "
              "are valid, rates are not measured here", flush=True)
    cks = [c for c in (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1000)
           if c <= args.steps]
    if cks[-1] != args.steps:
        cks.append(args.steps)
    print(f"{'steps':>6}  {'rel L2':>10}  {'rel max':>10}  "
          f"{'max(T) f32':>12}  {'max(T) bf16':>12}")
    for ck, l2, mx, pa, pb in error_curve(args.n, tuple(cks),
                                          schedule=args.schedule,
                                          vmem_chunk=args.vmem_chunk):
        print(f"{ck:6d}  {l2:10.4%}  {mx:10.4%}  {pa:12.6f}  {pb:12.6f}",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

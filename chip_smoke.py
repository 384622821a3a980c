"""The stencil main path once, on the chip, through its normal entry points.

    python chip_smoke.py               # one TPU chip: every phase below
    python chip_smoke.py --four-chips  # a 2x2 mesh: the sharded phase only

One process holds the chip and runs every phase in order; each phase
prints one line (what ran, its backend-compile seconds, its run seconds)
and raises on any failure. The last stdout line is the one JSON object

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

Without a TPU the script exits nonzero at the device check and prints no
result: there is no CPU or interpret-mode branch.

Phases (one chip):
  perf    HeatDiffusion `perf` at diffusion_2D_perf's 12288² f32 through
          the apps' own builder (apps/_common.build_config), vs the `ap`
          array program from the same initial condition.
  tb      The temporal-blocked `run_hbm_blocked` (k=8) at 12288², same check.
  vmem    The bench headline: `run_vmem_resident` at 252² (bench._bench_model).
  serve   Four 1024² diffusion requests through SimulationService.

Phase (--four-chips): `perf` and `hide` on a 2x2 mesh at the reference's
acceptance run (252² global, 1000 steps) and at 12288² per chip, each vs
the same global problem unsharded on one device, plus a check that each
of the four devices holds one shard.

Correctness bound (`_check`): the explicit scheme with dt under the CFL
limit makes each step a convex combination of neighbours, so max-norm
differences never grow; each path rounds once per step at <= 1/2 ulp of
max|T|. Two paths over nt steps therefore differ by at most
nt·eps32·max|T0|; the check allows twice that. At 12288² per chip the
field moves about one ulp a step, so that bound equals the field's whole
motion, and a result whose boundary slabs never advanced would still
pass it. The check therefore also holds the difference to REL_TOL of
how far the oracle moved from T0 (which implies the result moved too).
Sound chip runs read 0.025-0.045 of it in every phase (PR 21).
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

EPS32 = float(np.finfo(np.float32).eps)
REF_PERF_NT = 1000  # diffusion_2D_perf.jl's step count
REF_N = 12288  # diffusion_2D_perf.jl: fact=12 -> 12288² per rank
SMOKE_NT, SMOKE_WARMUP = 100, 10  # the cut of the reference's 1000 steps
TB_K = 8  # temporal-blocking depth (multi_step_cm_hbm)
VMEM_CHUNK = 16  # 1.85 s cold compile on the chip; chunk 256 took 108.7 s
SERVE_N, SERVE_REQUESTS, SERVE_NT = 1024, 4, 200
REL_TOL = 0.1  # max|T-oracle| / max|oracle-T0|, see the module docstring


def device_check(want: int | None = None) -> dict:
    """The chip, as JAX reports it; SystemExit (nonzero) without one."""
    import jax

    from rocm_mpi_tpu.utils.backend import require_accelerator

    dev = require_accelerator("chip_smoke.py")
    if want is not None and dev["count"] != want:
        raise SystemExit(
            f"chip_smoke: need {want} TPU devices, found {dev['count']}"
        )
    print(f"[device] {dev['kind']} x{dev['count']}, jax {jax.__version__}",
          flush=True)
    return dev


def _compile_s() -> float:
    """Backend-compile seconds so far (telemetry.compiles tracker)."""
    from rocm_mpi_tpu.telemetry import compiles

    progs = compiles.snapshot()["programs"]
    return sum(p["wall_s"] for p in progs.values())


def _timed(fn):
    """(result, wall seconds, backend-compile seconds) of fn()."""
    c0, t0 = _compile_s(), time.monotonic()
    out = fn()
    return out, time.monotonic() - t0, _compile_s() - c0


def config(n: int, nt: int, warmup: int, dims=(1, 1)):
    """A 2D f32 config through the apps' own builder (the one
    apps/diffusion_2d_perf.py runs), at n² global."""
    from apps._common import build_config, make_parser

    args = make_parser("perf", nx=n, ny=n, nt=nt, do_vis=False).parse_args([
        "--dtype", "f32", "--nt", str(nt), "--warmup", str(warmup),
        "--dims", ",".join(str(d) for d in dims),
    ])
    return build_config(args), args.driver


def _check(label: str, got, ref, T0, nt: int) -> str:
    """Raise unless `got` is finite and agrees with `ref` (see module
    docstring for the bound); return the evidence for the phase line."""
    import jax
    import jax.numpy as jnp

    ref = jax.device_put(ref, got.sharding)
    T0 = jax.device_put(T0, got.sharding)
    finite = bool(jnp.all(jnp.isfinite(got)))
    err = float(jnp.max(jnp.abs(got - ref)))
    moved_ref = float(jnp.max(jnp.abs(ref - T0)))
    tol = 2.0 * nt * EPS32 * float(jnp.max(jnp.abs(T0)))
    if not finite:
        raise AssertionError(f"{label}: non-finite values in the result")
    if not err <= tol:
        raise AssertionError(
            f"{label}: max|T-oracle|={err:.3e} exceeds tol {tol:.3e}"
        )
    if not err <= REL_TOL * moved_ref:
        raise AssertionError(
            f"{label}: max|T-oracle|={err:.3e} exceeds {REL_TOL} of the "
            f"oracle's motion {moved_ref:.3e} from T0"
        )
    return (f"max|T-oracle|={err:.3e} <= tol {tol:.3e}, "
            f"{err / moved_ref:.3f} of the oracle's motion {moved_ref:.3e}")


def _oracle(cfg, nt: int, devices=None):
    """(T after nt steps of the `ap` array program, T0), unsharded."""
    import dataclasses

    from rocm_mpi_tpu.models import HeatDiffusion

    ocfg = dataclasses.replace(cfg, dims=(1,) * cfg.ndim, nt=nt, warmup=0)
    model = HeatDiffusion(ocfg, devices=devices)
    T0 = model.init_state()[0]
    return model.run(variant="ap", nt=nt, warmup=0).T, T0


def _rate_line(r) -> str:
    steps = r.nt - r.warmup
    return (f"run {r.wtime:.4f} s timed ({steps} steps, "
            f"{r.wtime_it * 1e3:.4f} ms/step, {r.gpts:.3f} Gpts/s)")


def phase_perf(n: int = REF_N, nt: int = SMOKE_NT,
               warmup: int = SMOKE_WARMUP) -> str:
    """`perf` (one Pallas kernel per step) at n² vs the `ap` oracle."""
    from rocm_mpi_tpu.models import HeatDiffusion

    cfg, driver = config(n, nt, warmup)
    r, wall, comp = _timed(
        lambda: HeatDiffusion(cfg).run(variant="perf", driver=driver)
    )
    ref, T0 = _oracle(cfg, nt)
    ev = _check("perf", r.T, ref, T0, nt)
    return (f"[perf] {n}² f32 dims=(1,1) driver={driver} nt={nt} "
            f"(reference: {REF_PERF_NT}) warmup={warmup}: compile "
            f"{comp:.2f} s, {_rate_line(r)}, wall {wall:.2f} s; {ev}")


def phase_tb(n: int = REF_N, k: int = TB_K, nt: int = 13 * TB_K,
             warmup: int = TB_K) -> str:
    """Temporal-blocked HBM sweeps (k steps per pass) vs the oracle."""
    from rocm_mpi_tpu.models import HeatDiffusion

    cfg, _ = config(n, nt, warmup)
    r, wall, comp = _timed(
        lambda: HeatDiffusion(cfg).run_hbm_blocked(block_steps=k)
    )
    ref, T0 = _oracle(cfg, nt)
    ev = _check("tb", r.T, ref, T0, nt)
    return (f"[tb] {n}² f32 run_hbm_blocked k={k} nt={nt} warmup={warmup}: "
            f"compile {comp:.2f} s, {_rate_line(r)}, wall {wall:.2f} s; {ev}")


def phase_vmem(chunk: int = VMEM_CHUNK, nt: int = 65 * VMEM_CHUNK,
               warmup: int = VMEM_CHUNK) -> str:
    """The bench headline program (whole loop in one VMEM-resident
    kernel) at bench.BENCH_SHAPE vs the oracle."""
    from bench import BENCH_SHAPE, _bench_model

    model = _bench_model(nt, warmup)
    r, wall, comp = _timed(lambda: model.run_vmem_resident(chunk=chunk))
    ref, T0 = _oracle(model.config, nt)
    ev = _check("vmem", r.T, ref, T0, nt)
    n = BENCH_SHAPE[0]
    return (f"[vmem] {n}² f32 run_vmem_resident chunk={chunk} nt={nt} "
            f"warmup={warmup}: compile {comp:.2f} s, {_rate_line(r)}, "
            f"wall {wall:.2f} s; {ev}")


def phase_serve(n: int = SERVE_N, count: int = SERVE_REQUESTS,
                nt: int = SERVE_NT) -> str:
    """A few diffusion requests through the in-process service, the way
    apps/serve.py drives it: every ticket `done`, every field finite and
    n²; the ic_scale=1 lane agrees with the oracle."""
    from rocm_mpi_tpu.serving.queue import Request
    from rocm_mpi_tpu.serving.service import ServeConfig, SimulationService

    svc = SimulationService(config=ServeConfig())
    reqs = [
        Request(request_id=f"smoke-{i}", workload="diffusion",
                global_shape=(n, n), dtype="f32", nt=nt,
                ic_scale=1.0 + 0.25 * i)
        for i in range(count)
    ]
    tickets = [svc.queue.submit(r) for r in reqs]
    report, wall, comp = _timed(lambda: svc.run_trace([]))
    for t in tickets:
        if t.state != "done":
            raise AssertionError(
                f"serve: {t.request.request_id} ended {t.state!r}: {t.error}"
            )
        (field,) = t.result(timeout=0)
        if field.shape != (n, n) or not np.isfinite(field).all():
            raise AssertionError(
                f"serve: {t.request.request_id} returned a "
                f"{field.shape} field with non-finite values"
            )
    cfg, _ = config(n, nt, 0)
    ref, T0 = _oracle(cfg, tickets[0].steps_run)
    import jax.numpy as jnp

    ev = _check("serve", jnp.asarray(tickets[0].result(timeout=0)[0]),
                ref, T0, tickets[0].steps_run)
    return (f"[serve] {count} diffusion requests {n}² f32 nt={nt} through "
            f"SimulationService: {report.served} done in {report.n_bins} "
            f"bin(s), {report.n_programs} program(s); compile {comp:.2f} s, "
            f"wall {wall:.2f} s; lane 0 {ev}")


def phase_four_chips(n_local: int, nt: int, warmup: int) -> list[str]:
    """`perf` and `hide` on a 2x2 mesh at (2·n_local)² global vs the
    same problem unsharded on device 0; each device holds one shard."""
    import jax

    from rocm_mpi_tpu.models import HeatDiffusion

    n = 2 * n_local
    cfg, driver = config(n, nt, warmup, dims=(2, 2))
    ref = _oracle(cfg, nt, devices=jax.devices()[:1])[0]
    model = HeatDiffusion(cfg)
    ref = jax.device_put(ref, model.grid.sharding)  # frees device 0
    T0 = model.init_state()[0]
    lines = []
    for variant in ("perf", "hide"):
        r, wall, comp = _timed(
            lambda: model.run(variant=variant, driver=driver)
        )
        shards = {s.device: s.data.shape for s in r.T.addressable_shards}
        if set(shards) != set(jax.devices()) or set(shards.values()) != {
            (n_local, n_local)
        }:
            raise AssertionError(
                f"{variant}: shards {shards} are not one {n_local}² block "
                f"on each of the {len(jax.devices())} devices"
            )
        ev = _check(variant, r.T, ref, T0, nt)
        lines.append(
            f"[2x2 {variant}] {n}² global ({n_local}²/chip) f32 "
            f"driver={driver} nt={nt} warmup={warmup}: compile {comp:.2f} s, "
            f"{_rate_line(r)}, wall {wall:.2f} s; one shard on each of "
            f"{len(shards)} devices; {ev}"
        )
        del r
    return lines


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run only the 2x2 sharded phase (needs 4 chips)")
    args = p.parse_args(argv)

    dev = device_check(4 if args.four_chips else None)
    from rocm_mpi_tpu.telemetry import compiles
    from rocm_mpi_tpu.utils.backend import enable_persistent_cache

    enable_persistent_cache()
    compiles.install()
    if args.four_chips:
        phases = [
            # the reference's acceptance run (docs/Temp_4_252_252.png)
            lambda: phase_four_chips(126, REF_PERF_NT, SMOKE_WARMUP),
            lambda: phase_four_chips(REF_N, 20, 5),
        ]
    else:
        phases = [phase_perf, phase_tb, phase_vmem, phase_serve]
    for phase in phases:
        out = phase()
        for line in [out] if isinstance(out, str) else out:
            print(line, flush=True)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""2D/3D shallow-water app — the coupled-multi-field workload driver.

Runs models.swe.ShallowWater on the same launch/report skeleton as the
diffusion and wave apps. No reference analog (the reference ships one
physics model); alongside the wave app this is the worked example of
docs/ADDING_A_MODEL.md at the app layer. Reports the closed-basin mass
drift — the workload's exact invariant — the way the diffusion apps report
the max(T) decay invariant.
"""

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from _common import OUTPUT_DIR, setup_jax  # noqa: E402


def make_parser():
    import argparse

    from _common import nonneg_int, positive_int

    p = argparse.ArgumentParser(
        description="2D/3D linear shallow water — forward-backward C-grid"
    )
    p.add_argument("--nx", type=int, default=252)
    p.add_argument("--ny", type=int, default=252)
    p.add_argument(
        "--nz", type=nonneg_int, default=0,
        help="z grid points (0 or 1 = 2D, matching init_global_grid's "
        "squeeze of trailing size-1 axes)",
    )
    p.add_argument("--nt", type=int, default=1000)
    p.add_argument("--warmup", type=int, default=10)
    p.add_argument("--dtype", default="f64", choices=["f32", "f64", "bf16"])
    p.add_argument("--dims", default=None, help="process grid, e.g. 2,2")
    p.add_argument("--cpu-devices", type=int, default=0, metavar="N")
    p.add_argument(
        "--variant", default="perf", choices=["ap", "perf", "hide"]
    )
    sched = p.add_mutually_exclusive_group()
    sched.add_argument(
        "--deep", type=positive_int, default=0, metavar="K",
        help="deep-halo sweeps: exchange the width-K ghosts of the whole "
        "coupled state once per K steps instead of width-1 every step",
    )
    sched.add_argument(
        "--vmem", action="store_true",
        help="whole-loop-in-VMEM fast path (single device only)",
    )
    p.add_argument("--vis", action="store_true")
    p.add_argument(
        "--vis-shards", action="store_true",
        help="also render one panel per device shard (the "
        "poc_rocmaware.png-style halo-exchange proof; 2D + --vis only)",
    )
    p.add_argument(
        "--profile", default=None, metavar="DIR",
        help="trace the timed loop with jax.profiler into DIR (the "
        "--profile convention of the diffusion apps, SURVEY.md §5.1)",
    )
    p.add_argument(
        "--save-field", default=None, metavar="PATH.npy",
        help="dump the final gathered surface height as .npy on process 0 "
        "(the machine-readable artifact, SURVEY.md §5.4)",
    )
    from _common import (
        add_checkpoint_flags,
        add_telemetry_flag,
        add_wire_mode_flag,
    )

    add_wire_mode_flag(p)
    add_telemetry_flag(p)
    add_checkpoint_flags(p)
    return p


def build_config(args):
    """The app's SWEConfig from its parsed arguments."""
    from rocm_mpi_tpu.models.swe import SWEConfig

    dims = tuple(int(d) for d in args.dims.split(",")) if args.dims else None
    shape = (args.nx, args.ny) + ((args.nz,) if args.nz > 1 else ())
    return SWEConfig(
        global_shape=shape,
        lengths=(10.0,) * len(shape),
        nt=args.nt,
        warmup=args.warmup,
        dtype=args.dtype,
        dims=dims,
        wire_mode=args.wire_mode,
    )


def main(argv=None) -> int:
    args = make_parser().parse_args(argv)
    jax = setup_jax(args)

    import jax.numpy as jnp

    from rocm_mpi_tpu.models.swe import ShallowWater
    from rocm_mpi_tpu.parallel import gather_to_host0
    from rocm_mpi_tpu.utils import viz
    from rocm_mpi_tpu.utils.logging import log0

    cfg = build_config(args)
    model = ShallowWater(cfg)
    grid = model.grid
    log0(
        f"Process {grid.me} grid {grid.global_shape} over mesh {grid.dims} "
        f"({grid.nprocs} device(s): {jax.devices()[0].device_kind} …)"
    )
    h0, _ = model.init_state()
    mass0 = float(jnp.sum(h0, dtype=jnp.float64))
    # One chain decides label AND runner together (the _common.py
    # convention: artifacts must identify the schedule that actually ran).
    if args.checkpoint:
        if args.vmem:
            log0("--checkpoint supports the per-step and deep schedules; "
                 "drop --vmem")
            return 2
        from _common import checkpoint_schedule, make_checkpoint_runner

        from rocm_mpi_tpu.models.swe import SWERunResult

        make_advance, quantum, label = checkpoint_schedule(
            args, model, args.variant,
            lambda: model.advance_fn(args.variant),
        )

        def advance_state():
            advance = make_advance()
            h1, us1 = model.init_state()
            Mus = model.face_masks()
            return (
                lambda s, n: tuple(advance(s[0], s[1], Mus, n)),
                (h1, us1),
            )

        runner = make_checkpoint_runner(
            args, log0, advance_state,
            lambda s, ran, wtime: SWERunResult(
                h=s[0], us=s[1], wtime=wtime, nt=ran, warmup=0, config=cfg
            ),
            quantum=quantum,
        )
    elif args.deep:
        k_eff = model.effective_deep_depth(block_steps=args.deep, warn=False)
        label = f"deep{k_eff}"
        log0(f"--deep: running deep-halo sweeps (k={k_eff}) instead of "
             "the per-step variant")
        runner = lambda: model.run_deep(block_steps=k_eff)
    elif args.vmem:
        if grid.nprocs != 1:
            log0("--vmem requires a single-device grid (the whole-loop-in-"
                 f"VMEM path is unsharded); mesh is {grid.dims}")
            return 2
        label = "vmem"
        log0("--vmem: running the whole-loop-in-VMEM fast path instead of "
             "the per-step variant")
        runner = model.run_vmem_resident
    else:
        label = args.variant
        runner = lambda: model.run(variant=args.variant)
    from _common import profile_context

    profile_ctx = profile_context(jax, args)
    log0("Starting the time loop 🚀...", end="")
    with profile_ctx:
        result = runner()
    log0("done")
    from _common import report_checkpointed_line

    report_checkpointed_line(result, args, log0)
    mass = float(jnp.sum(result.h, dtype=jnp.float64))
    log0(
        f"mass drift = {abs(mass - mass0) / abs(mass0):.3e} "
        "(closed basin: conserved up to storage-dtype rounding)"
    )
    if args.vis and cfg.ndim != 2:
        log0("--vis is 2D-only (heatmap); skipping the artifact")
        args.vis = False
    h_v = (
        gather_to_host0(result.h)
        if (args.vis or args.save_field)
        else None
    )
    if args.save_field and h_v is not None:
        import numpy as np

        out = pathlib.Path(args.save_field)
        out.parent.mkdir(parents=True, exist_ok=True)
        np.save(out, h_v)
        log0(f"wrote {out}")
    if args.vis:
        if h_v is not None:
            path = OUTPUT_DIR / viz.artifact_name(
                f"swe_{label}", grid.nprocs, grid.global_shape
            )
            viz.save_heatmap(
                h_v, path,
                title=f"swe {label} nt={result.nt} mesh={grid.dims}",
            )
            log0(f"wrote {path}")
            if args.vis_shards and grid.ndim == 2:
                # signed: h oscillates around 0 — symmetric limits, or the
                # troughs clip to flat colormap-bottom and hide seams.
                ppath = viz.save_shard_panels_artifact(
                    h_v, grid, f"swe_{label}", OUTPUT_DIR, signed=True
                )
                log0(f"wrote {ppath}")
    else:
        log0(f"maximum(|h|) = {float(jnp.abs(result.h).max())}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

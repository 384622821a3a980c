"""Shared CLI driver for the diffusion apps (reference L6/L1 analog).

The reference's five apps share an identical skeleton — init grid, IC, hot
loop, T_eff printout, gather + heatmap (SURVEY.md §3). Here that skeleton is
one driver parameterized by variant; each app file pins its variant and
defaults, exactly as runme.sh selects which .jl to run
(/root/reference/scripts/runme.sh:5-9).
"""

from __future__ import annotations

import argparse
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

OUTPUT_DIR = pathlib.Path(__file__).resolve().parent.parent / "output"


def positive_int(v):
    """argparse type: int >= 1 (shared by the workload app parsers)."""
    import argparse

    i = int(v)
    if i < 1:
        raise argparse.ArgumentTypeError(f"must be >= 1, got {v}")
    return i


def nonneg_int(v):
    """argparse type: int >= 0 (shared by the workload app parsers)."""
    import argparse

    i = int(v)
    if i < 0:
        raise argparse.ArgumentTypeError(f"must be >= 0, got {v}")
    return i


def make_parser(
    variant: str, *, nx: int, ny: int, nt: int, do_vis: bool, nz: int = 0
):
    ndim = "3D" if nz else "2D"
    p = argparse.ArgumentParser(
        description=f"{ndim} heat diffusion — {variant} variant"
    )
    p.add_argument("--nx", type=int, default=nx, help="global grid points, x")
    p.add_argument("--ny", type=int, default=ny, help="global grid points, y")
    p.add_argument(
        "--nz", type=int, default=nz, help="global grid points, z (0 = 2D)"
    )
    p.add_argument(
        "--fact",
        type=int,
        default=0,
        help="if set, every grid axis becomes fact*1024 "
        "(perf.jl:21 'fact' knob; in 3D this includes nz)",
    )
    p.add_argument("--nt", type=int, default=nt, help="time steps")
    p.add_argument("--warmup", type=int, default=10, help="untimed steps")
    p.add_argument(
        "--dtype", default="f64", choices=["f32", "f64", "bf16"],
        help="f64 matches the reference; f32 is the TPU fast path",
    )
    p.add_argument(
        "--dims", default=None,
        help="process grid, e.g. 2,2 (default: auto near-square)",
    )
    p.add_argument(
        "--cpu-devices", type=int, default=0, metavar="N",
        help="simulate N virtual CPU devices instead of real hardware",
    )
    vis = p.add_mutually_exclusive_group()
    vis.add_argument("--vis", dest="do_vis", action="store_true", default=do_vis)
    vis.add_argument("--no-vis", dest="do_vis", action="store_false")
    p.add_argument(
        "--vis-shards", action="store_true",
        help="also render one panel per shard (the poc_rocmaware.png-style "
        "halo-exchange proof; 2D + --vis only)",
    )
    p.add_argument(
        "--transport", default=None, choices=["ici", "host"],
        help="halo transport: device-direct collectives vs host staging "
        "(IGG_ROCMAWARE_MPI=1/0 analog)",
    )
    p.add_argument(
        "--profile", default=None, metavar="DIR",
        help="trace the timed loop with jax.profiler into DIR",
    )
    p.add_argument(
        "--deep", type=int, default=0, metavar="K",
        help="use deep-halo sweeps: exchange width-K ghosts every K steps "
        "instead of width-1 every step (parallel.deep_halo; f32/bf16)",
    )
    add_wire_mode_flag(p)
    add_driver_flag(p)
    p.add_argument(
        "--save-field", default=None, metavar="PATH.npy",
        help="dump the final gathered field as .npy (process 0)",
    )
    add_telemetry_flag(p)
    add_health_flag(p)
    add_checkpoint_flags(p)
    return p


def add_wire_mode_flag(p) -> None:
    """The shared --wire-mode knob (docs/PERF.md "Wire precision"): the
    halo exchange's on-wire slab precision. The stateful int8 modes are
    deep-halo-only (the per-step programs are stateless); telemetry
    stamps the mode on every exchange annotation and run gauge so
    reduced-wire summaries can't be regress-compared to f32 ones."""
    from rocm_mpi_tpu.parallel.wire import WIRE_MODES

    p.add_argument(
        "--wire-mode", default="f32", choices=list(WIRE_MODES),
        help="on-wire halo slab precision (default f32 — bitwise-"
        "identical exchange; bf16 halves the wire; int8/int8_delta "
        "quantize with error feedback and need --deep)",
    )


def add_driver_flag(p) -> None:
    """The shared --driver knob: which multi-step loop form runs the
    per-step variants. "scan" (default) is the donation-aware lax.scan
    driver (models.*.scan_advance_fn — allocation-free steady state);
    "step" the classic per-step fori_loop. Results are bitwise identical;
    telemetry stamps the form so summaries from different drivers can't
    be compared silently."""
    p.add_argument(
        "--driver", default="scan", choices=["step", "scan"],
        help="multi-step loop form for per-step variants (default: scan, "
        "the donation-aware lax.scan driver); --deep and --checkpoint "
        "schedules have their own loop forms and ignore this",
    )


def add_telemetry_flag(p) -> None:
    """The shared --telemetry block (docs/TELEMETRY.md): every workload
    app and the weak-scaling harness expose the same knob."""
    p.add_argument(
        "--telemetry", default=None, metavar="DIR",
        help="collect structured telemetry (spans/counters/events) into "
        "DIR as telemetry-rank{k}.jsonl; merge and inspect with "
        "`python -m rocm_mpi_tpu.telemetry summarize DIR` "
        "(RMT_TELEMETRY_DIR is the env spelling the launcher forwards)",
    )


def setup_telemetry(args, jax) -> None:
    """Enable telemetry when --telemetry DIR was given (env-configured
    collection — the launcher's RMT_TELEMETRY_DIR — needs no call here;
    events reads the env at import). Called after distributed init so
    the rank stamp is the real process index. A telemetry-enabled run
    also installs the compile tracker (telemetry/compiles.py): compile
    spans and the recompile accounting ride the same stream."""
    from rocm_mpi_tpu import telemetry

    if getattr(args, "telemetry", None):
        telemetry.configure(
            directory=args.telemetry, enabled=True,
            rank=jax.process_index(),
        )
    if telemetry.enabled():
        from rocm_mpi_tpu.telemetry import compiles

        compiles.install()


def add_health_flag(p) -> None:
    """The shared --health knob (docs/TELEMETRY.md "Health plane")."""
    p.add_argument(
        "--health", action="store_true",
        help="run the per-rank flight recorder: progress counters + a "
        "heartbeat-rank{k}.json sidecar (atomic, watchdog/monitor-"
        "readable even while this rank is blocked in a collective) and "
        "a SIGUSR2 faulthandler post-mortem hook; needs a telemetry "
        "directory (--telemetry DIR or the launcher env) for the "
        "sidecars (RMT_HEALTH=1 is the env spelling spawn_ranks "
        "forwards)",
    )


def setup_health(args, jax) -> None:
    """Arm the flight recorder when --health was given or the launcher
    contract says so (RMT_HEALTH, forwarded by spawn_ranks health_dir).
    Called after distributed init + setup_telemetry: the sidecar rank
    stamp must be the real process index, and the default sidecar home
    is the telemetry sink."""
    from rocm_mpi_tpu.telemetry import flight

    try:
        if getattr(args, "health", False):
            flight.enable(rank=jax.process_index())
        elif not flight.enable_from_env():
            return
    except ValueError as e:
        # Both spellings (--health flag, RMT_HEALTH env) fail the same
        # clean way when no sidecar directory is configured.
        raise SystemExit(f"--health / RMT_HEALTH: {e}") from None
    flight.install_postmortem_handler()
    # flight.enable may have just armed telemetry collection (health
    # implies it) AFTER setup_telemetry's install gate ran — re-check,
    # or a health-only run would mark/emit compile gauges with no
    # tracker listening and bank fabricated zeros.
    from rocm_mpi_tpu import telemetry

    if telemetry.enabled():
        from rocm_mpi_tpu.telemetry import compiles

        compiles.install()


def add_checkpoint_flags(p) -> None:
    """The shared --checkpoint/--ckpt-every/--resume/--retries/
    --inject-fault block (SURVEY.md §5.4 upgraded + the resilience layer
    — utils/checkpoint.py and rocm_mpi_tpu/resilience/ have the design)."""
    p.add_argument(
        "--checkpoint", default=None, metavar="DIR",
        help="periodically checkpoint the run state into DIR (orbax, "
        "sharded save); the run becomes durable against preemption",
    )
    p.add_argument(
        "--ckpt-every", type=positive_int, default=None, metavar="N",
        help="checkpoint interval in steps (default: nt/4)",
    )
    p.add_argument(
        "--resume", action="store_true",
        help="with --checkpoint: continue from the latest VALID saved "
        "step in DIR (corrupt/truncated checkpoints are skipped) instead "
        "of the initial condition",
    )
    p.add_argument(
        "--retries", type=nonneg_int, default=0, metavar="N",
        help="with --checkpoint: supervise the run — on a crash/backend "
        "error, restore the latest valid checkpoint and retry with "
        "exponential backoff, up to N restarts (resilience.run_supervised)",
    )
    p.add_argument(
        "--inject-fault", default=None, metavar="SPEC",
        help="deterministic fault injection for drills/tests, e.g. "
        "'crash@step=12' or 'truncate-latest@segment=2' "
        "(rocm_mpi_tpu/resilience/faults.py has the grammar)",
    )


def checkpointed_run(args, advance, init_state, log0, quantum: int = 1):
    """--checkpoint mode: segmented advance with orbax saves between
    segments; --resume restores the latest step first. `advance(state, n)
    -> state` is the framework's standard traced-step-count contract, so
    all segments share one compiled program. Returns
    (final_state, steps_run_here, wtime) — wtime spans the segmented loop
    INCLUDING save time (this is the durability mode, not the benchmark
    protocol; the reported rate says so).

    `quantum` is the schedule's step granularity (the deep schedule
    advances k steps per sweep): the save interval is rounded UP to a
    multiple of it, so segment lengths never truncate a sweep."""
    from rocm_mpi_tpu.utils import checkpoint as ckpt
    from rocm_mpi_tpu.utils.metrics import Timer

    every = args.ckpt_every or max(args.nt // 4, 1)
    if every % quantum:
        rounded = ((every // quantum) + 1) * quantum
        log0(f"--ckpt-every {every} rounded to {rounded} (the schedule "
             f"advances {quantum} steps at a time)")
        every = rounded
    supervised = getattr(args, "retries", 0) > 0
    start = 0
    state = init_state
    if args.resume:
        # The latest VALID step (integrity manifest checked): a corrupt
        # or truncated checkpoint falls back to the previous kept step
        # instead of being restored — or worse, trusted.
        latest = ckpt.latest_valid_step(args.checkpoint, log=log0)
        # `is not None`, not truthiness: the contract is int | None, and
        # a (hypothetical) step-0 checkpoint must restore, not silently
        # fall through to the initial condition.
        if latest is not None:
            start = latest
            if not supervised:
                log0(f"--resume: restoring step {latest} from "
                     f"{args.checkpoint}")
                state = ckpt.restore_state(args.checkpoint, latest,
                                           init_state)
        else:
            log0(f"--resume: no checkpoint under {args.checkpoint}; "
                 "starting from the initial condition")
    # A checkpoint written by a different schedule/nt can land on a step
    # the current schedule cannot reach exactly (the deep advance moves k
    # steps per sweep and its trip count floors — a misaligned window
    # would silently drop up to k-1 trailing steps). Refuse loudly.
    if start % quantum or (args.nt - start) % quantum:
        log0(
            f"--resume: checkpoint step {start} / window {args.nt - start} "
            f"is not a multiple of the schedule's step quantum {quantum} "
            "(was this checkpoint written by a different schedule or nt?); "
            "resume with the schedule that wrote it or adjust --nt"
        )
        raise SystemExit(2)
    if start >= args.nt and not supervised:
        log0(f"--resume: checkpoint already at step {start} >= nt={args.nt};"
             " nothing to run")
        return state, 0, 0.0
    # Labeled Timer: the interval lands in the telemetry stream as a
    # "run.checkpointed" span (durability window: advance + saves — the
    # per-save attribution comes from checkpoint.py's own spans). The
    # bare perf_counter() this replaces is now lint-gated (GL06).
    with Timer(label="run.checkpointed", steps=args.nt - start) as timer:
        if supervised:
            # Crash supervision (resilience.run_supervised): restore, the
            # nothing-to-run case, and retry restarts are all owned by the
            # supervisor — the app only pre-resolved `start` for the
            # quantum guard above and the steps-run accounting below.
            from rocm_mpi_tpu.resilience import run_supervised

            log0(f"supervised run: up to {args.retries} restart(s), "
                 f"resume={'on' if args.resume else 'off'}")
            state = run_supervised(
                advance, init_state, args.nt, args.checkpoint, every,
                max_retries=args.retries, resume=args.resume, log=log0,
            )
        else:
            state = ckpt.run_segmented(
                advance, state, args.nt, args.checkpoint, every,
                start_step=start,
            )
    wtime = timer.elapsed
    ran = max(args.nt - start, 0)
    if ran:
        log0(f"checkpointed {start}→{args.nt} every {every} steps into "
             f"{args.checkpoint}")
    return state, ran, wtime


def checkpoint_schedule(args, model, per_step_label, make_per_step):
    """The one chooser for checkpoint mode's schedule: returns
    (make_advance, quantum, label). With --deep it builds the model's
    deep advance ONCE and uses the k that deep_advance_fn itself returns
    (single source — label, quantum, and executed depth cannot diverge);
    otherwise the per-step variant with quantum 1."""
    if getattr(args, "deep", 0):
        advance, k = model.deep_advance_fn(
            block_steps=args.deep, nt=args.nt, warmup=0
        )
        return (lambda: advance), k, f"ckpt_deep{k}"
    return make_per_step, 1, f"ckpt_{per_step_label}"


def make_checkpoint_runner(args, log0, advance_state, make_result,
                           quantum: int = 1):
    """The one checkpoint-mode runner shared by the workload apps:
    `advance_state() -> (adv, init_state)` builds the model's segmented
    advance (the standard `adv(state, n) -> state` contract) and
    `make_result(state, ran, wtime)` wraps the outcome in the workload's
    RunResult type with `nt=ran, warmup=0` — nt of 0 signals the
    nothing-to-run case (resume already complete), which
    report_checkpointed_line then reports WITHOUT touching the rate
    properties (t_eff would divide by the zero wall time)."""

    def runner():
        adv, init_state = advance_state()
        state, ran, wtime = checkpointed_run(
            args, adv, init_state, log0, quantum=quantum
        )
        return make_result(state, ran, wtime)

    return runner


def report_checkpointed_line(result, args, log0) -> None:
    """The checkpoint-aware 'Executed …' report: rates only when steps
    actually ran (a fully-resumed run has nt=0 and zero wall time)."""
    if getattr(args, "checkpoint", None) and result.nt == 0:
        log0("0 steps run (checkpoint already complete); state restored")
        return
    log0(
        f"Executed {result.nt} steps in = {result.wtime:.3e} sec "
        f"(@ T_eff = {result.t_eff:.2f} GB/s aggregate, "
        f"{result.gpts:.4f} Gpts/s)"
    )
    if getattr(args, "checkpoint", None):
        log0("(durability mode: wall time includes checkpoint saves — "
             "not the benchmark protocol)")


def setup_jax(args):
    import jax

    from rocm_mpi_tpu.parallel.distributed import maybe_initialize_distributed

    if getattr(args, "inject_fault", None):
        # Before distributed init: the "init" fault point (delay-rank
        # drills) fires inside maybe_initialize_distributed.
        from rocm_mpi_tpu.resilience import faults

        faults.install(args.inject_fault)
    maybe_initialize_distributed()
    if args.cpu_devices:
        from rocm_mpi_tpu.utils.backend import set_cpu_device_count

        jax.config.update("jax_platforms", "cpu")
        set_cpu_device_count(args.cpu_devices)
    if args.dtype == "f64":
        jax.config.update("jax_enable_x64", True)
    # Persistent compile cache: an app re-run skips the Mosaic compiles
    # an earlier run already paid; on CPU it is a no-op unless the test
    # harness opts in (RMT_CPU_CACHE=1 — see utils.backend), where it
    # stops the suite's subprocess app tests re-paying identical XLA:CPU
    # compiles every run.
    from rocm_mpi_tpu.utils.backend import enable_persistent_cache

    enable_persistent_cache()
    setup_telemetry(args, jax)
    setup_health(args, jax)
    # Preemption awareness (resilience.preempt, docs/RESILIENCE.md §7):
    # arm the SIGTERM grace-deadline handler when the launcher contract
    # says so (RMT_PREEMPT_GRACE_S, forwarded by spawn_ranks
    # preempt_grace_s) — cheap no-op otherwise. Installation lives in
    # resilience/ (a GL07 signal-hygiene owner); this is only the call.
    from rocm_mpi_tpu.resilience import preempt

    preempt.install_from_env()
    return jax


def profile_context(jax, args):
    """The one --profile idiom (SURVEY.md §5.1): a jax.profiler trace over
    the timed loop when --profile DIR was given, a no-op otherwise. Shared
    by run_app and the wave app so the profiling convention cannot
    diverge between workloads."""
    import contextlib

    if getattr(args, "profile", None):
        return jax.profiler.trace(args.profile)
    return contextlib.nullcontext()


def build_config(args):
    from rocm_mpi_tpu.config import DiffusionConfig, with_fact

    dims = None
    if args.dims:
        dims = tuple(int(d) for d in args.dims.split(","))
    kwargs = {}
    if args.transport:
        kwargs["halo_transport"] = args.transport
    if getattr(args, "wire_mode", None):
        kwargs["wire_mode"] = args.wire_mode
    if getattr(args, "b_width", None):
        kwargs["b_width"] = tuple(int(b) for b in args.b_width.split(","))
    shape = (args.nx, args.ny)
    if getattr(args, "nz", 0):
        shape += (args.nz,)
    cfg = DiffusionConfig(
        global_shape=shape,
        lengths=(10.0,) * len(shape),
        nt=args.nt,
        warmup=args.warmup,
        dtype=args.dtype,
        dims=dims,
        do_vis=args.do_vis,
        **kwargs,
    )
    if args.fact:
        cfg = with_fact(cfg, args.fact)
    return cfg


def emit_run_gauges(result, variant: str, driver: str | None = None,
                    wire: str | None = None) -> None:
    """Bank the run's headline rates into the telemetry stream (no-op
    when collection is off; rate properties divide by the timed window,
    so a fully-resumed nt=0 run emits nothing). `driver` stamps the loop
    form (step/scan) and `wire` the on-wire halo precision on the
    gauges, so summaries from different drivers or wire modes can't be
    compared silently (aggregate folds non-f32 wire into the gauge key,
    like the driver suffix)."""
    from rocm_mpi_tpu import telemetry

    if not telemetry.enabled() or not result.nt or not result.wtime:
        return
    attrs = {"variant": variant}
    if driver is not None:
        attrs["driver"] = driver
    if wire is not None:
        attrs["wire"] = wire
    telemetry.gauge("run.gpts", result.gpts, **attrs)
    telemetry.gauge("run.t_eff_gbs", result.t_eff, **attrs)


def run_app(variant: str, args) -> int:
    """The shared skeleton: init → run → report → (gather + heatmap)."""
    jax = setup_jax(args)
    import numpy as np

    from rocm_mpi_tpu.models import HeatDiffusion
    from rocm_mpi_tpu.parallel import gather_to_host0
    from rocm_mpi_tpu.utils import viz
    from rocm_mpi_tpu.utils.logging import log0

    cfg = build_config(args)
    model = HeatDiffusion(cfg)
    grid = model.grid
    log0(
        f"Process {grid.me} grid {grid.global_shape} over mesh {grid.dims} "
        f"({grid.nprocs} device(s): {jax.devices()[0].device_kind} …)"
    )

    profile_ctx = profile_context(jax, args)
    ckpt_mode = bool(getattr(args, "checkpoint", None))
    if getattr(args, "deep", 0):
        # The deep-halo schedule replaces the variant's own step entirely
        # (variant-specific knobs like --b-width are unused); label the
        # run and its artifacts with the depth that will actually execute
        # — the model's own accounting, so label and executed k cannot
        # drift (run_deep degrades k when the step counts aren't
        # divisible). Checkpoint mode has no warmup window, so its k is
        # gcd'd against nt alone — computed here so label and executed
        # depth agree in that mode too.
        k_eff = model.effective_deep_depth(
            warmup=0 if ckpt_mode else None,
            block_steps=args.deep, warn=False,
        )
        variant = f"deep{k_eff}"
        log0(f"--deep: running deep-halo sweeps (k={k_eff}"
             + (f", degraded from {args.deep}" if k_eff != args.deep else "")
             + ") instead of the per-step variant")
    if ckpt_mode:
        from rocm_mpi_tpu.models.diffusion import RunResult

        per_step = variant  # bind before the label rebinding below
        make_advance, quantum, variant = checkpoint_schedule(
            args, model, per_step, lambda: model.advance_fn(per_step)
        )

        def advance_state():
            advance = make_advance()
            return (
                lambda s, n: (advance(s[0], s[1], n), s[1]),
                model.init_state(),
            )

        runner = make_checkpoint_runner(
            args, log0, advance_state,
            lambda s, ran, wtime: RunResult(
                T=s[0], wtime=wtime, nt=ran, warmup=0, config=cfg
            ),
            quantum=quantum,
        )
        with profile_ctx:
            result = runner()
        report_checkpointed_line(result, args, log0)
        emit_run_gauges(result, variant,
                        wire=getattr(args, "wire_mode", None))
    else:
        log0("Starting the time loop 🚀...", end="")
        driver = getattr(args, "driver", "step")
        with profile_ctx:
            if getattr(args, "deep", 0):
                # The deep schedule is its own loop form (k-step sweeps);
                # --driver selects among the per-step loop forms only.
                # Stamp "deep" — the same spelling weak_scaling uses — so
                # the two harnesses' gauges land under one key.
                result = model.run_deep(
                    block_steps=args.deep,
                    wire_mode=getattr(args, "wire_mode", None),
                )
                driver = "deep"
            else:
                result = model.run(variant=variant, driver=driver)
        log0("done")

        per_chip = result.t_eff / grid.nprocs
        log0(
            f"Executed {result.nt} steps in = {result.wtime:.3e} sec "
            f"(@ T_eff = {result.t_eff:.2f} GB/s aggregate, "
            f"{per_chip:.2f} GB/s/chip, {result.gpts:.4f} Gpts/s)"
        )
        emit_run_gauges(result, variant, driver=driver,
                        wire=getattr(args, "wire_mode", None))

    T_v = (
        gather_to_host0(result.T)
        if (cfg.do_vis or getattr(args, "save_field", None))
        else None
    )
    if cfg.do_vis:
        if T_v is not None:
            log0(f"maximum(T_v) = {T_v.max()}")  # decay invariant (hide.jl:115)
            path = OUTPUT_DIR / viz.artifact_name(
                variant, grid.nprocs, grid.global_shape
            )
            viz.save_heatmap(
                T_v, path, title=f"{variant} nt={result.nt} mesh={grid.dims}"
            )
            log0(f"wrote {path}")
            if getattr(args, "vis_shards", False) and grid.ndim == 2:
                ppath = viz.save_shard_panels_artifact(
                    T_v, grid, variant, OUTPUT_DIR
                )
                log0(f"wrote {ppath}")
    else:
        # Cheap scalar invariant even without vis: peak must decay.
        log0(f"maximum(T) = {float(result.T.max())}")

    if getattr(args, "save_field", None) and T_v is not None:
        # The persistence artifact (SURVEY.md §5.4: the reference's only
        # persisted outputs are the PNG and prof.txt; the .npy dump is the
        # machine-readable equivalent).
        out = pathlib.Path(args.save_field)
        out.parent.mkdir(parents=True, exist_ok=True)
        np.save(out, T_v)
        log0(f"wrote {out}")
    return 0

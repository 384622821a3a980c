"""Weak-scaling harness — the driver-baseline north-star measurement.

Target (BASELINE.md): the overlap variant at 252²/device on a pod slice at
≥90% weak-scaling efficiency vs single chip. This harness holds the local
shard size fixed, grows the global grid with the device count (the same
weak-scaling protocol as the reference's per-rank-constant grids,
/root/reference/scripts/diffusion_2D_perf.jl:21-22 — 12288² *per rank*),
and reports per-device throughput and efficiency vs the 1-device run.

On real multi-chip hardware this measures the target directly. On one chip
(or `--cpu-devices N` virtual devices) it exercises the full sharded code
path — mesh construction, ppermute halo, overlap scheduling — so the
scaling *mechanics* are testable anywhere, as with everything else in this
framework.

  python apps/weak_scaling.py --cpu-devices 8        # 1,2,4,8 virtual devs
  python apps/weak_scaling.py --local 252 --variant hide   # real hardware
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))


def telemetry_windowed_run(model, variant: str, nt: int, warmup: int,
                           windows: int, driver: str = "step",
                           step_base: int = 0, config: str | None = None):
    """The --telemetry run path (diffusion): the same warmup/timed
    protocol as model.run, but the timed loop split into `windows`
    spanned windows — per-step PERCENTILES need more than the single
    sample model.run's one timed window gives (aggregate's p50/p90/p99
    over windows is what catches a straggling stretch the mean hides).
    Each window boundary costs one device sync (the span's
    correctness requirement); windows of many steps amortize it, exactly
    as tic/toc always did.

    `driver` picks the loop form (step/scan — models run the same step
    program either way); the scan driver's static chunk q quantizes the
    windows (every window a multiple of q, guaranteed non-degenerate by
    q | gcd(warmup, timed)), and every span carries the driver stamp so
    summaries from different drivers can't be compared silently.

    Under the health plane (--health / RMT_HEALTH, flight recorder on)
    each window boundary additionally runs, in this order: (1) the halo
    heartbeat probe — one REAL cross-rank exchange under a
    `halo.heartbeat` span, a live probe of the collective fabric whose
    entry is the last thing a rank wedging at the boundary records;
    (2) the "window" fault point (deterministic drills: the `stall`
    kind wedges a rank right here); (3) the flight-recorder step bump,
    flushed to the sidecar BEFORE this rank enters the window's
    compiled collectives. The bump-after-fault-point order is what
    makes the watchdog's stalled-collective signature deterministic: a
    rank stalled at boundary K never publishes step K, while its peer
    publishes K and then blocks inside window K — the cross-rank median
    moves past the victim and names it (telemetry.health). `step_base`
    offsets the published steps by this process's earlier ladder rungs
    (run or sat out), keeping flight step counters COMPARABLE across
    ranks — the watchdog's contract; a rung-local restart would be
    masked by the recorder's monotonic guard and skew every later
    comparison.
    After warmup (and the heartbeat's own compile) the run calls
    `compiles.mark_steady()`: every later XLA compile counts as a
    steady-state recompile, banked as the `compiles.steady_state` gauge
    the regress gate pins at 0."""
    from rocm_mpi_tpu.models.diffusion import RunResult
    from rocm_mpi_tpu.resilience import faults
    from rocm_mpi_tpu.telemetry import compiles, flight
    from rocm_mpi_tpu.utils import metrics

    if not 0 <= warmup < nt:
        # Same contract as model.run: a degenerate window must fail
        # loudly here, not as a later divide-by-zero or a negative rate.
        raise ValueError(f"need 0 <= warmup < nt, got {warmup}, {nt}")
    if driver == "scan":
        advance, unit = model.scan_advance_fn(variant, nt=nt, warmup=warmup,
                                              config=config)
    else:
        advance, unit = model.advance_fn(variant), 1
    T, Cp = model.init_state()
    from rocm_mpi_tpu import telemetry

    with telemetry.span("warmup", steps=warmup, variant=variant,
                        driver=driver) as sp:
        if warmup:
            T = advance(T, Cp, warmup)
        sp.sync(T)
    heartbeat = None
    if flight.enabled():
        from rocm_mpi_tpu.telemetry import probes

        heartbeat = probes.make_halo_heartbeat(model)
        T = heartbeat(T)  # eat the heartbeat's compile inside warmup
    if warmup:
        # The warmup line: the window program and the heartbeat are
        # compiled; anything XLA compiles after this is a recompile.
        # A --warmup 0 run has no warmup line to draw — the window
        # program's FIRST compile would land inside the steady window
        # and fail the zero-pin gate with no actual recompile storm —
        # so such runs simply don't pin steady state (the gauge is
        # only emitted once a window was ever opened).
        compiles.mark_steady()
    timed = nt - warmup
    n_windows = max(1, min(windows, timed // unit))
    base, extra = divmod(timed // unit, n_windows)
    wtime = 0.0
    done = warmup
    for i in range(n_windows):
        w = (base + (1 if i < extra else 0)) * unit
        if w == 0:
            continue
        if heartbeat is not None:
            T = heartbeat(T)
        faults.fault_point("window", step=done)
        flight.progress(step=step_base + done, windows=1)
        timer = metrics.Timer(label="step_window", phase="step", steps=w,
                              variant=variant, window=i, driver=driver,
                              workload="diffusion")
        timer.tic(T)
        T = advance(T, Cp, w)
        timer.toc(T)
        wtime += timer.elapsed
        done += w
    flight.progress(step=step_base + done)
    # Close this rung's steady window: the NEXT rung's mesh/shape
    # compiles are legitimate warmup, not steady-state recompiles.
    compiles.unmark_steady()
    return RunResult(T=T, wtime=wtime, nt=nt, warmup=warmup,
                     config=model.config)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--local", type=int, default=252,
                   help="per-device shard edge (target geometry: 252)")
    p.add_argument("--nt", type=int, default=2000)
    p.add_argument("--warmup", type=int, default=200)
    p.add_argument("--variant", default=None,
                   choices=["ap", "fused", "shard", "perf", "kp", "hide",
                            "deep"],
                   help="step schedule; 'deep' = deep-halo sweeps "
                   "(run_deep, the flagship multi-chip schedule). "
                   "Default: hide (both workloads)")
    p.add_argument("--workload", default="diffusion",
                   choices=["diffusion", "wave", "swe"],
                   help="physics model: the diffusion flagship, the "
                   "acoustic-wave second workload, or the shallow-water "
                   "coupled workload (non-diffusion variants "
                   "ap/perf/hide/deep)")
    p.add_argument("--deep-k", type=int, default=None, metavar="K",
                   help="deep-halo sweep depth (default: run_deep's auto)")
    p.add_argument("--dtype", default="f32")
    p.add_argument("--cpu-devices", type=int, default=0, metavar="N")
    p.add_argument("--counts", default=None,
                   help="comma-separated device counts (default: powers of 2 "
                   "up to all available)")
    p.add_argument("--json", action="store_true",
                   help="emit one JSON line per count as well")
    from _common import (
        add_driver_flag,
        add_health_flag,
        add_telemetry_flag,
        setup_jax,
    )

    add_driver_flag(p)
    add_telemetry_flag(p)
    add_health_flag(p)
    p.add_argument("--telemetry-windows", type=int, default=8, metavar="W",
                   help="with --telemetry: split the timed loop into W "
                   "spanned windows (per-step percentiles need more than "
                   "one sample; default %(default)s)")
    p.add_argument("--no-probes", dest="probes", action="store_false",
                   default=True,
                   help="with --telemetry: skip the halo/interior/"
                   "checkpoint phase-attribution probes "
                   "(telemetry.probes)")
    p.add_argument("--autotune", action="store_true",
                   help="consult the persistent tuning cache "
                   "(config='auto', docs/PERF.md 'Autotuning'): the scan "
                   "chunk and deep-halo depth resolve per "
                   "(shape, dtype, topology, backend) key, falling back "
                   "to the hand defaults on a miss; cache hit/miss and "
                   "the chosen configs land in the run gauges "
                   "(tune.hits/tune.misses) so `telemetry regress` can "
                   "gate tuned-vs-default ladders")
    args = p.parse_args(argv)

    jax = setup_jax(args)  # distributed init + --cpu-devices + x64, shared
    from rocm_mpi_tpu.config import DiffusionConfig
    from rocm_mpi_tpu.utils.logging import log0
    from rocm_mpi_tpu.models import (
        AcousticWave,
        HeatDiffusion,
        ShallowWater,
        SWEConfig,
        WaveConfig,
    )
    from rocm_mpi_tpu.parallel.mesh import suggest_dims

    if args.variant is None:
        args.variant = "hide"
    if args.workload != "diffusion" and args.variant not in (
        "ap", "perf", "hide", "deep"
    ):
        log0(f"--workload {args.workload} supports variants "
             f"ap/perf/hide/deep, not {args.variant!r}")
        return 2

    n_avail = len(jax.devices())
    if args.counts:
        # Ascending, deduplicated: the first row run IS the efficiency
        # baseline, so the smallest count must come first.
        counts = sorted({int(c) for c in args.counts.split(",")})
    else:
        counts, c = [], 1
        while c <= n_avail:
            counts.append(c)
            c *= 2
    base_per_dev = base_n = None
    probe_model = None
    # Global flight-step offset across the ladder (health plane): every
    # rung this process ran — or sat out before its first participation
    # — banks its nt, so participating ranks' step counters stay
    # comparable rung after rung (the watchdog's contract; a sat-out
    # rung never publishes, which the read side treats as
    # "not participating", never as "stalled").
    steps_banked = 0
    # The loop-form stamp every gauge/probe carries (the deep schedule is
    # its own form; --driver only selects among the per-step loop forms).
    run_driver = "deep" if args.variant == "deep" else args.driver
    # Process-0-gated output: on a multi-host slice every process runs this
    # script, but only one may report (rank-0 printing, SURVEY.md §5.5).
    log0(
        f"weak scaling: variant={args.variant}, {args.local}²/device, "
        f"nt={args.nt}, dtype={args.dtype}, {n_avail} device(s) available"
    )
    for n in counts:
        if n > n_avail:
            log0(f"n={n}: skipped (only {n_avail} devices)")
            continue
        if not any(
            d.process_index == jax.process_index()
            for d in jax.devices()[:n]
        ):
            # A rung whose submesh holds none of this process's devices:
            # this process cannot allocate on it (jax refuses a device
            # assignment with no local devices) and the compute is
            # entirely local to the owning process(es) — sit the rung out
            # (banking its steps for the health plane's global counter).
            steps_banked += args.nt
            continue
        dims = suggest_dims(n, 2)
        shape = (args.local * dims[0], args.local * dims[1])
        common = dict(
            global_shape=shape,
            lengths=(10.0 * dims[0], 10.0 * dims[1]),
            nt=args.nt,
            warmup=args.warmup,
            dtype=args.dtype,
            dims=dims,
        )
        model_cls, cfg_cls = {
            "wave": (AcousticWave, WaveConfig),
            "swe": (ShallowWater, SWEConfig),
            "diffusion": (HeatDiffusion, DiffusionConfig),
        }[args.workload]
        model = model_cls(cfg_cls(**common), devices=jax.devices()[:n])
        from rocm_mpi_tpu import telemetry

        run_config = "auto" if args.autotune else None
        if args.variant == "deep":
            # Both models default None to their own depth policy and
            # reject explicit invalid depths loudly. --autotune lets an
            # unset depth consult the tuning cache (diffusion only — the
            # other models keep their own policies).
            if args.workload == "diffusion":
                r = model.run_deep(block_steps=args.deep_k,
                                   config=run_config)
            else:
                r = model.run_deep(block_steps=args.deep_k)
        elif (telemetry.enabled() and args.workload == "diffusion"
              and model.config.halo_transport != "host"):
            # The windowed path drives the advance directly; under
            # halo_transport='host' that would silently measure the
            # device-collective path while labeling it a host run —
            # model.run owns the host-staged dispatch and its warning.
            # GL08: this run IS reachable under the rank-dependent rung
            # sit-out above — by design: a sitting-out process owns no
            # device of the rung's submesh, so the rung's collectives
            # span only the participating processes' devices and every
            # participant still issues the identical sequence.
            # graftlint: disable-next=GL08
            r = telemetry_windowed_run(
                model, args.variant, args.nt, args.warmup,
                args.telemetry_windows, driver=args.driver,
                step_base=steps_banked, config=run_config,
            )
        else:
            r = model.run(variant=args.variant, driver=args.driver,
                          config=run_config)
        steps_banked += args.nt
        probe_model = model  # the last rung this process participated in
        per_dev = r.gpts / n
        if base_per_dev is None:
            # The efficiency baseline is the smallest count actually run;
            # the north-star "vs single chip" number requires n=1 in the
            # list, so label the baseline explicitly.
            base_per_dev, base_n = per_dev, n
        eff = per_dev / base_per_dev
        # The driver stamp rides every gauge: a "scan"-driver summary and
        # a "step"-driver summary are different measurements and must not
        # regress-gate against each other silently.
        if telemetry.enabled():
            telemetry.gauge("run.gpts", round(r.gpts, 6), devices=n,
                            variant=args.variant, workload=args.workload,
                            driver=run_driver)
            telemetry.gauge("run.gpts_per_device", round(per_dev, 6),
                            devices=n, driver=run_driver)
            telemetry.gauge("run.efficiency", round(eff, 6), devices=n,
                            driver=run_driver)
        log0(
            f"n={n:4d} mesh={dims} global={shape}: "
            f"{r.wtime_it * 1e6:9.3f} us/step  {r.gpts:9.4f} Gpts/s "
            f"({per_dev:7.4f}/dev)  efficiency={eff:6.1%} vs n={base_n}"
        )
        if args.json and jax.process_index() == 0:
            wl = "" if args.workload == "diffusion" else f"{args.workload} "
            row = {
                "metric": f"weak-scaling {wl}{args.variant} "
                          f"{args.local}²/dev",
                "devices": n, "dims": dims, "gpts": round(r.gpts, 4),
                "gpts_per_device": round(per_dev, 4),
                "efficiency": round(eff, 4),
            }
            if jax.devices()[0].platform == "cpu":
                # Interpret-mode rates are meaningless; without this stamp
                # a committed jsonl row's bare `efficiency` reads as a
                # performance claim (VERDICT r4 weak #6). Real-hardware
                # rows omit the key and ARE the claim.
                row["mechanics_only"] = True
            print(json.dumps(row))

    from rocm_mpi_tpu import telemetry

    if telemetry.enabled():
        # Compile accounting, banked BEFORE the phase probes below: the
        # probes compile their own halo/interior programs on purpose,
        # and those deliberate epilogue compiles must not show up as
        # steady-state recompiles in the gauge the regress gate pins.
        from rocm_mpi_tpu.telemetry import compiles

        compiles.emit_gauges()
        # Autotuner resolve outcomes (tune.hits/tune.misses + per-key
        # tune.resolve annotations): a tuned ladder and a hand-default
        # ladder are different measurements — the gauges say which this
        # was, so regress never compares them silently.
        from rocm_mpi_tpu.tuning import resolve as tuning_resolve

        tuning_resolve.emit_gauges()

    if (telemetry.enabled() and args.probes and probe_model is not None
            and args.workload == "diffusion"):
        # Phase attribution for the fused step (telemetry/probes.py):
        # halo-only and interior-only programs over the final rung's
        # state, plus one save/restore cycle for the checkpoint phase.
        # Participation bookkeeping: process sets grow monotonically with
        # the rung's device count, so every process with a probe_model
        # participated in the final rung and holds that rung's model —
        # the halo/interior probes are mesh-scoped collectives among
        # exactly those processes (the same shape every rung already
        # runs). The ORBAX save is different: its completion barrier is
        # GLOBAL across all jax processes, so the checkpoint probe only
        # runs when the probe mesh spans every process — a host whose
        # devices sat out the whole ladder must not be waited on.
        from rocm_mpi_tpu.telemetry import events as tel_events
        from rocm_mpi_tpu.telemetry import probes

        tel_dir = tel_events.directory()
        mesh_procs = {
            d.process_index
            for d in probe_model.grid.mesh.devices.flat
        }
        spans_all = mesh_procs == set(range(jax.process_count()))
        ckpt_dir = (
            pathlib.Path(tel_dir) / "ckpt-probe"
            if tel_dir and spans_all else None
        )
        log0("telemetry: running halo/interior"
             + ("/checkpoint" if ckpt_dir else "")
             + " phase probes")
        probes.run_diffusion_phase_probes(
            probe_model, checkpoint_dir=ckpt_dir, driver=run_driver,
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The compiled HBM-traffic gate CLI (docs/PERF.md).

    python -m rocm_mpi_tpu.perf [--local N] [--devices N] [--deep-k K]
                                [--budgets PATH] [--json]
                                [--include-waste-fixture]
                                [--include-wire-fixture] [--no-wire]

CPU-only by construction: it pins the CPU backend, builds a small
virtual-device mesh, lowers + compiles each distributed step driver, and
gates the modeled bytes-per-invocation (and exact collective wire bytes)
against the committed budgets in rocm_mpi_tpu/perf/budgets.json. It then
runs the wire-bytes ladder (docs/PERF.md "Wire precision"): one deep
sweep compiled per wire mode, its exact collective send bytes held to
the mode's closed-form ideal AND the committed ladder fraction of the
full-precision wire (--no-wire skips it; --include-wire-fixture audits
the doctored over-ladder regression row, which must fail).

Exit codes: 0 every audited variant within budget; 1 any variant over
budget (or over the wire ideal); 2 usage/internal error. Runs in tier-1
and scripts/lint.sh — no accelerator, no timing, no flakiness.
"""

from __future__ import annotations

import argparse
import json
import sys


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m rocm_mpi_tpu.perf",
        description=__doc__.splitlines()[0],
    )
    p.add_argument("--local", type=int, default=None,
                   help="per-device shard edge (default: budgets geometry)")
    p.add_argument("--devices", type=int, default=None,
                   help="virtual CPU devices (default: budgets geometry)")
    p.add_argument("--deep-k", type=int, default=None,
                   help="deep sweep depth (default: budgets geometry)")
    p.add_argument("--budgets", default=None, metavar="PATH",
                   help="budgets file (default: rocm_mpi_tpu/perf/budgets.json)")
    p.add_argument("--json", action="store_true",
                   help="emit one JSON line per row on stdout (table goes "
                   "to stderr)")
    p.add_argument("--include-waste-fixture", action="store_true",
                   help="also audit the known-waste concatenate-splice "
                   "fixture (regression-tests the gate itself; EXPECTED "
                   "to fail, so the exit code goes 1)")
    p.add_argument("--include-wire-fixture", action="store_true",
                   help="also audit the doctored over-ladder wire row "
                   "(a full-precision program claiming the bf16 ladder "
                   "row; regression-tests the wire-bytes ladder — "
                   "EXPECTED to fail, so the exit code goes 1)")
    p.add_argument("--no-wire", action="store_true",
                   help="skip the wire-bytes ladder (docs/PERF.md 'Wire "
                   "precision'); the ladder runs by default")
    p.add_argument("--include-batch-fixture", action="store_true",
                   help="also audit the doctored over-padded batched row "
                   "(a 4-wide batched program carrying one live lane; "
                   "regression-tests the batched-step audit, docs/"
                   "SERVING.md — EXPECTED to fail, so the exit code "
                   "goes 1)")
    p.add_argument("--no-batch", action="store_true",
                   help="skip the batched-step audit (docs/SERVING.md); "
                   "it runs by default")
    args = p.parse_args(argv)

    # CPU pinning BEFORE any backend use: the gate must neither need nor
    # touch an accelerator.
    import jax

    from rocm_mpi_tpu.utils.backend import set_cpu_device_count

    from rocm_mpi_tpu.perf import traffic

    try:
        budgets = traffic.load_budgets(args.budgets)
    except (OSError, ValueError) as e:
        print(f"perf: cannot load budgets: {e}", file=sys.stderr)
        return 2
    geo = budgets.get("geometry", {})
    local = args.local or int(geo.get("local", traffic.DEFAULT_LOCAL))
    deep_k = args.deep_k or int(geo.get("deep_k", traffic.DEFAULT_DEEP_K))
    dims = tuple(int(d) for d in geo.get("dims", (2, 1)))
    if args.devices:
        from rocm_mpi_tpu.parallel.mesh import suggest_dims

        dims = suggest_dims(args.devices, 2)

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)
    import math

    set_cpu_device_count(max(2, math.prod(dims)))

    rows = traffic.audit_variants(
        local=local, dims=dims, deep_k=deep_k, budgets=budgets,
        include_waste_fixture=args.include_waste_fixture,
    )
    if not args.no_batch:
        # The multi-tenant batched-step audit (docs/SERVING.md): the
        # B-lane program's bytes per invocation vs B × the single-lane
        # ideal; rows render/gate alongside the per-variant audit.
        serving_geo = budgets.get("serving", {})
        rows += traffic.audit_batched(
            local=local, dims=dims,
            batch=int(serving_geo.get("batch", traffic.DEFAULT_BATCH)),
            budgets=budgets,
            include_batch_fixture=args.include_batch_fixture,
        )
    wire_rows = []
    if not args.no_wire:
        wire_geo = budgets.get("wire", {})
        wire_rows = traffic.audit_wire_modes(
            local=int(wire_geo.get("local", traffic.DEFAULT_WIRE_LOCAL)),
            dims=dims,
            deep_k=int(wire_geo.get("deep_k",
                                    traffic.DEFAULT_WIRE_DEEP_K)),
            budgets=budgets,
            include_wire_fixture=args.include_wire_fixture,
        )
    table = traffic.render_table(rows)
    if wire_rows:
        table += "\n\n" + traffic.render_wire_table(wire_rows)
    if args.json:
        print(table, file=sys.stderr)
        for r in rows:
            print(json.dumps({
                "metric": f"traffic {r.variant}", "steps": r.steps,
                "bytes": r.measured_bytes, "ideal": r.ideal_bytes,
                "ratio": round(r.ratio, 4), "wire": r.wire_bytes,
                "wire_ideal": r.wire_ideal, "budget": r.budget,
                "ok": r.ok,
            }))
        for w in wire_rows:
            print(json.dumps({
                "metric": f"wire {w.mode}", "bytes": w.wire_bytes,
                "full_ideal": w.full_ideal, "mode_ideal": w.mode_ideal,
                "fraction": round(w.fraction, 4), "ladder": w.ladder,
                "ok": w.ok,
            }))
    else:
        print(table)
    bad = [r for r in rows if not r.ok]
    bad_wire = [w for w in wire_rows if not w.ok]
    if bad or bad_wire:
        msgs = [
            f"{r.variant} ({r.ratio:.2f}x vs "
            f"{r.budget if r.budget is not None else '—'}"
            f"{'' if r.wire_ok else ', wire over ideal'})"
            for r in bad
        ] + [
            f"wire {w.mode} ({w.fraction:.3f} of the f32 wire vs ladder "
            f"{w.ladder if w.ladder is not None else '—'})"
            for w in bad_wire
        ]
        print("perf: TRAFFIC GATE FAILED — " + ", ".join(msgs),
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

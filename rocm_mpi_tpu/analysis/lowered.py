"""Ground-truth lowered-program audit: prove, from the compiled HLO,
what GL08/GL01 assert from the source.

The AST engine (analysis/engine.py) reasons about what the *Python*
will trace; this module checks what XLA actually *lowered* — the
steady-state drivers of all three workloads (diffusion / wave / SWE,
the same entry-point harness perf/traffic.py audits) are compiled on a
small virtual-CPU mesh and the optimized module is parsed for:

(a) **collective-sequence identity across rank-roles.** The per-role
    sequence is materialized per partition: every collective the role
    executes, in program order (while-loop bodies included — the scan
    and fori drivers keep their exchanges there), keyed by (op kind,
    channel id). The sequences must be identical for every role, which
    concretely requires no collective under a `conditional` branch
    computation (a lowered rank-divergent collective — GL08's hazard
    surviving to the executable), every collective channel-numbered,
    and permute source/target pair structures forming at most one
    send + one receive per partition.

(b) **real donation aliasing.** Every GL01-declared donation
    (`donate_argnums` on the driver) must appear in the module's
    `input_output_alias` table. jax drops an inapplicable donation
    with a warning CI never reads; a "donated" driver that silently
    copies is both a perf lie (the traffic budgets assume in-place
    ghost-write chains) and a masked GL01 hazard (the name is safe to
    re-read precisely because nothing aliased — until jax changes its
    mind).

Wired as a lint.sh gate stage (`python -m rocm_mpi_tpu.analysis.lowered`)
next to the HBM-traffic gate: CPU-only, no timing, deterministic. This
is the one analysis module that imports jax — and only inside the audit
entry points, never at import time.
"""

from __future__ import annotations

import dataclasses
import re

# ---------------------------------------------------------------------------
# HLO text parsing (stdlib-only: usable on canned fixtures without jax)
# ---------------------------------------------------------------------------

COLLECTIVE_OPS = frozenset({
    "collective-permute", "all-reduce", "all-gather", "all-to-all",
    "reduce-scatter", "collective-broadcast", "collective-permute-start",
    "all-reduce-start", "all-gather-start",
})

_COMP_RE = re.compile(
    r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->\s*.*\{$"
)
_OP_RE = re.compile(r"^(?:ROOT\s+)?%[\w.\-]+\s*=\s*(.*?)\s([\w\-]+)\(")
_CHANNEL_RE = re.compile(r"\bchannel_id=(\d+)")
_PAIRS_RE = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)*)\}")
_PAIR_RE = re.compile(r"\{(\d+),(\d+)\}")
_SUBCOMP_RE = re.compile(
    r"\b(?:calls|body|condition|to_apply|true_computation|"
    r"false_computation)=%([\w.\-]+)"
)
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_NUM_PARTITIONS_RE = re.compile(r"\bnum_partitions=(\d+)")
_ALIAS_TABLE_RE = re.compile(r"input_output_alias=\{(.*?)\}\s*,\s*entry")
_ALIAS_ENTRY_RE = re.compile(
    r"\{[\d,\s]*\}:\s*\((\d+),\s*\{[\d,\s]*\}(?:,\s*(?:may|must)-alias)?\)"
)


@dataclasses.dataclass(frozen=True)
class CollectiveOp:
    kind: str
    channel: int | None
    pairs: tuple  # ((src, tgt), ...) for permutes, () otherwise
    in_conditional: bool
    loop_depth: int
    line: str  # the HLO line, for reporting


@dataclasses.dataclass
class _HloOp:
    kind: str
    line: str
    subcomps: tuple
    branch_comps: tuple


def _parse_computations(hlo_text: str) -> tuple[dict, str | None, int]:
    """(computations, entry name, num_partitions): computation name ->
    ordered [_HloOp]. Scheduled HLO is flat — computations are not
    nested — so a simple line scanner is exact."""
    comps: dict[str, list] = {}
    entry = None
    current: list | None = None
    for raw in hlo_text.splitlines():
        line = raw.strip()
        if current is None:
            m = _COMP_RE.match(line)
            if m:
                name = m.group(2)
                comps[name] = current = []
                if m.group(1):
                    entry = name
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _OP_RE.match(line)
        if not m:
            continue
        body = line.split(", metadata=")[0]
        branches = _BRANCHES_RE.search(body)
        current.append(_HloOp(
            kind=m.group(2),
            line=line,
            subcomps=tuple(_SUBCOMP_RE.findall(body)),
            branch_comps=tuple(
                n.strip().lstrip("%")
                for n in branches.group(1).split(",")
            ) if branches else (),
        ))
    header = hlo_text.splitlines()[0] if hlo_text else ""
    m = _NUM_PARTITIONS_RE.search(header)
    nparts = int(m.group(1)) if m else 1
    return comps, entry, nparts


def collective_sequence(hlo_text: str) -> list[CollectiveOp]:
    """Every collective reachable from ENTRY, in program order, with
    its execution context (inside a conditional branch? how many loop
    bodies deep?)."""
    comps, entry, _ = _parse_computations(hlo_text)
    if entry is None:
        return []
    out: list[CollectiveOp] = []

    def visit(comp_name: str, in_conditional: bool, loop_depth: int,
              depth: int) -> None:
        if depth > 16:  # malformed/cyclic input: stop, never hang
            return
        for op in comps.get(comp_name, ()):
            if op.kind in COLLECTIVE_OPS:
                ch = _CHANNEL_RE.search(op.line)
                pm = _PAIRS_RE.search(op.line.split(", metadata=")[0])
                pairs = tuple(
                    (int(a), int(b))
                    for a, b in _PAIR_RE.findall(pm.group(1))
                ) if pm else ()
                out.append(CollectiveOp(
                    kind=op.kind,
                    channel=int(ch.group(1)) if ch else None,
                    pairs=pairs,
                    in_conditional=in_conditional,
                    loop_depth=loop_depth,
                    line=op.line[:160],
                ))
            is_loop = op.kind == "while"
            is_cond = op.kind == "conditional"
            for sub in op.subcomps:
                visit(sub, in_conditional or is_cond,
                      loop_depth + (1 if is_loop else 0), depth + 1)
            for sub in op.branch_comps:
                visit(sub, True, loop_depth, depth + 1)

    visit(entry, False, 0, 0)
    return out


def aliased_params(hlo_text: str) -> set[int]:
    """Entry-parameter numbers the module's input_output_alias table
    maps to an output — the donations XLA actually honored."""
    header = hlo_text.splitlines()[0] if hlo_text else ""
    m = _ALIAS_TABLE_RE.search(header)
    if not m:
        return set()
    return {int(p) for p in _ALIAS_ENTRY_RE.findall(m.group(1))}


# ---------------------------------------------------------------------------
# Role-sequence audit
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RoleAudit:
    """Per-rank-role collective sequences + the identity verdict."""

    num_partitions: int
    sequence: list  # CollectiveOp, program order
    role_sequences: dict  # role -> [(kind, channel)]
    problems: list

    @property
    def ok(self) -> bool:
        return not self.problems


def audit_roles(hlo_text: str) -> RoleAudit:
    comps_seq = collective_sequence(hlo_text)
    _, _, nparts = _parse_computations(hlo_text)
    problems: list[str] = []
    roles = list(range(nparts))
    role_sequences: dict[int, list] = {r: [] for r in roles}
    for op in comps_seq:
        if op.in_conditional:
            problems.append(
                f"collective under a conditional branch (a lowered "
                f"rank-divergent collective): {op.line}"
            )
            continue  # cannot attribute it to every role
        if op.channel is None:
            problems.append(
                f"collective without channel_id (cross-partition order "
                f"unpinned): {op.line}"
            )
        if op.kind.startswith("collective-permute") and op.pairs:
            srcs = [s for s, _ in op.pairs]
            tgts = [t for _, t in op.pairs]
            if len(srcs) != len(set(srcs)) or len(tgts) != len(set(tgts)):
                problems.append(
                    f"permute pair structure is not a partial "
                    f"permutation: {op.pairs}"
                )
            outside = [p for p in srcs + tgts if p >= nparts]
            if outside:
                problems.append(
                    f"permute names partitions outside the mesh "
                    f"({outside} >= {nparts}): {op.line}"
                )
        for r in roles:
            role_sequences[r].append((op.kind, op.channel))
    # No set-compare of the materialized role sequences: a single SPMD
    # module IS every partition's program, so the per-role lists are
    # identical by construction and such a check could never fire. The
    # cross-role identity verdict lives in the checks above — the only
    # ways one lowered module diverges per role are a collective under a
    # conditional (flagged, and excluded from the attributed sequences),
    # an unpinned channel order, or a malformed permute pair structure.
    return RoleAudit(
        num_partitions=nparts,
        sequence=comps_seq,
        role_sequences=role_sequences,
        problems=problems,
    )


# ---------------------------------------------------------------------------
# Donation audit
# ---------------------------------------------------------------------------


def expected_donated_params(args, donate_argnums) -> set[int]:
    """Flattened entry-parameter indices of the donated arguments (jit
    flattens args in order; each donated pytree covers a contiguous
    leaf range)."""
    import jax

    donated: set[int] = set()
    offset = 0
    wanted = set(donate_argnums)
    for i, arg in enumerate(args):
        n = len(jax.tree_util.tree_leaves(arg))
        if i in wanted:
            donated.update(range(offset, offset + n))
        offset += n
    return donated


def audit_donation(hlo_text: str, args, donate_argnums) -> list[str]:
    """Problems (empty = every declared donation actually aliased)."""
    aliased = aliased_params(hlo_text)
    expected = expected_donated_params(args, donate_argnums)
    missing = sorted(expected - aliased)
    if missing:
        return [
            f"declared donations not aliased by XLA (params {missing}; "
            f"alias table covers {sorted(aliased)}) — the driver "
            "silently copies what GL01 assumes it consumes"
        ]
    return []


# ---------------------------------------------------------------------------
# The three workloads' steady-state drivers
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class DriverAudit:
    workload: str
    num_partitions: int
    n_collectives: int
    donated_params: int
    problems: list

    @property
    def ok(self) -> bool:
        return not self.problems


def _compiled_text(jitted, *args) -> str:
    return jitted.lower(*args).compile().as_text()


def audit_drivers(local: int = 16, steps: int = 2) -> list[DriverAudit]:
    """Compile + audit each workload's steady-state driver on the
    current (CPU) backend over a 2×1 mesh — the same geometry class the
    traffic gate uses, at a smaller shard so the full lint.sh stage
    stays well inside its budget. Callers own backend pinning
    (main() / tests set JAX_PLATFORMS=cpu + virtual devices)."""
    import jax.numpy as jnp

    from rocm_mpi_tpu.config import DiffusionConfig
    from rocm_mpi_tpu.models import (
        AcousticWave,
        HeatDiffusion,
        ShallowWater,
        SWEConfig,
        WaveConfig,
    )

    dims = (2, 1)
    shape = (local * dims[0], local * dims[1])
    lengths = (10.0 * dims[0], 10.0 * dims[1])
    rows: list[DriverAudit] = []

    def audit(workload, text, args, donate_argnums):
        roles = audit_roles(text)
        problems = list(roles.problems)
        if not roles.sequence:
            problems.append(
                "no collectives in the lowered program (the distributed "
                "driver audited away its exchanges?)"
            )
        problems += audit_donation(text, args, donate_argnums)
        rows.append(DriverAudit(
            workload=workload,
            num_partitions=roles.num_partitions,
            n_collectives=len(roles.sequence),
            donated_params=len(
                expected_donated_params(args, donate_argnums)
            ),
            problems=problems,
        ))

    # diffusion: the fused shard step (the per-step program the drivers
    # execute; donate=True is their steady-state aliasing)
    m = HeatDiffusion(DiffusionConfig(
        global_shape=shape, lengths=lengths, nt=8, warmup=0,
        dtype="f64", dims=dims,
    ))
    T, Cp = m.init_state()
    step, prepare = m.prepared_step_fn("shard", donate=True)
    C = prepare(Cp)
    audit("diffusion/shard", _compiled_text(step, T, C), (T, C), (0,))

    # wave: the fori-loop advance (collectives live in the while body)
    w = AcousticWave(WaveConfig(
        global_shape=shape, lengths=lengths, nt=8, warmup=0, dims=dims,
    ))
    U, Uprev, C2 = w.init_state()
    adv = w.advance_fn("perf")
    wargs = (U, Uprev, C2, jnp.int64(steps))
    audit("wave/perf", _compiled_text(adv, *wargs), wargs, (0, 1))

    # SWE: the coupled-state advance (h + (u, v) donated, masks not)
    s = ShallowWater(SWEConfig(
        global_shape=shape, lengths=lengths, nt=8, warmup=0, dims=dims,
    ))
    h, us = s.init_state()
    Mus = s.face_masks()
    sadv = s.advance_fn("perf")
    sargs = (h, us, Mus, jnp.int64(steps))
    audit("swe/perf", _compiled_text(sadv, *sargs), sargs, (0, 1))

    return rows


def audit_batched_drivers(local: int = 16, batch: int = 2,
                          steps: int = 2) -> list[DriverAudit]:
    """Compile + audit the SERVING layer's steady-state programs — the
    multi-tenant batched advances (models.*.batched_advance_fn, the
    exact callables `serving/service._Program.advance` executes per
    batch) plus the diffusion batched-hide edition — on the current
    (CPU) backend over a space×batch mesh (batch rows 1, space 2×1).

    The donation verdict is the serving pipeline's allocation
    contract (docs/SERVING.md "The pipeline"): every batched state
    leaf is declared donated, and a declared-but-unaliased donation
    would mean steady-state serving silently allocates a full batch of
    state per drain batch — the perf lie the `input_output_alias`
    check turns into a lint-stage failure. The collective checks ride
    along: the batched exchange's permutes must stay per-space-axis
    partial permutations (nothing ever permutes over `batch`), outside
    any lowered conditional."""
    import jax
    import numpy as np

    from rocm_mpi_tpu.config import DiffusionConfig
    from rocm_mpi_tpu.models import (
        AcousticWave,
        HeatDiffusion,
        ShallowWater,
        SWEConfig,
        WaveConfig,
    )

    dims = (2, 1)
    shape = (local * dims[0], local * dims[1])
    lengths = (10.0 * dims[0], 10.0 * dims[1])
    rows: list[DriverAudit] = []

    def audit(workload, text, args, donate_argnums):
        roles = audit_roles(text)
        problems = list(roles.problems)
        if not roles.sequence:
            problems.append(
                "no collectives in the lowered program (the batched "
                "driver audited away its exchanges?)"
            )
        problems += audit_donation(text, args, donate_argnums)
        rows.append(DriverAudit(
            workload=workload,
            num_partitions=roles.num_partitions,
            n_collectives=len(roles.sequence),
            donated_params=len(
                expected_donated_params(args, donate_argnums)
            ),
            problems=problems,
        ))

    def put(a, s):
        return jax.device_put(np.asarray(a), s)

    lane_steps = np.full(batch, steps, np.int32)

    # diffusion (one donated leaf), shard + the batched-hide overlap
    m = HeatDiffusion(DiffusionConfig(
        global_shape=shape, lengths=lengths, nt=8, warmup=0,
        dtype="f64", dims=dims, b_width=(local // 4, local // 4),
    ))
    T0, Cp = m.init_state()
    Tn = np.asarray(T0)
    for variant in ("shard", "hide"):
        adv, bg = m.batched_advance_fn(batch=batch, variant=variant)
        args = (
            put(np.stack([Tn] * batch), bg.sharding),
            put(Cp, bg.aux_sharding),
            put(lane_steps, bg.batch_sharding),
            steps,
        )
        audit(f"diffusion/batched-{variant}",
              _compiled_text(adv, *args), args, (0,))

    # wave (both leapfrog carries donated)
    w = AcousticWave(WaveConfig(
        global_shape=shape, lengths=lengths, nt=8, warmup=0, dims=dims,
    ))
    U0, _, C2 = w.init_state()
    Un = np.asarray(U0)
    wadv, wbg = w.batched_advance_fn(batch=batch)
    wargs = (
        put(np.stack([Un] * batch), wbg.sharding),
        put(np.stack([Un] * batch), wbg.sharding),
        put(C2, wbg.aux_sharding),
        put(lane_steps, wbg.batch_sharding),
        steps,
    )
    audit("wave/batched", _compiled_text(wadv, *wargs), wargs, (0, 1))

    # SWE (h + every velocity leaf donated; the face masks are not)
    s = ShallowWater(SWEConfig(
        global_shape=shape, lengths=lengths, nt=8, warmup=0, dims=dims,
    ))
    h0, us0 = s.init_state()
    Mus = s.face_masks()
    hn = np.asarray(h0)
    sadv, sbg = s.batched_advance_fn(batch=batch)
    zeros_b = np.zeros((batch,) + shape)
    sargs = (
        put(np.stack([hn] * batch), sbg.sharding),
        tuple(put(zeros_b, sbg.sharding) for _ in us0),
        tuple(put(M, sbg.aux_sharding) for M in Mus),
        put(lane_steps, sbg.batch_sharding),
        steps,
    )
    audit("swe/batched", _compiled_text(sadv, *sargs), sargs, (0, 1))

    return rows


def render_table(rows: list[DriverAudit]) -> str:
    head = (
        f"{'workload':16s} {'parts':>5s} {'collectives':>11s} "
        f"{'donated':>7s} status"
    )
    lines = [head, "-" * len(head)]
    for r in rows:
        status = "ok" if r.ok else "DIVERGENT/UNALIASED"
        lines.append(
            f"{r.workload:16s} {r.num_partitions:5d} "
            f"{r.n_collectives:11d} {r.donated_params:7d} {status}"
        )
        for p in r.problems:
            lines.append(f"    problem: {p}")
    return "\n".join(lines)


def main(argv=None) -> int:
    import argparse
    import sys

    p = argparse.ArgumentParser(
        prog="python -m rocm_mpi_tpu.analysis.lowered",
        description="lowered-program audit: identical collective "
                    "sequences across rank-roles + real donation "
                    "aliasing on every workload's steady-state driver",
    )
    p.add_argument("--local", type=int, default=16,
                   help="per-device shard edge (default 16 — the audit "
                   "judges structure, not size)")
    p.add_argument("--json", action="store_true",
                   help="one JSON line per driver on stdout (table to "
                   "stderr)")
    args = p.parse_args(argv)

    # CPU pinning BEFORE any backend use — same contract as the traffic
    # gate: no accelerator needed or touched.
    import jax

    from rocm_mpi_tpu.utils.backend import set_cpu_device_count

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)
    set_cpu_device_count(2)

    rows = audit_drivers(local=args.local)
    rows += audit_batched_drivers(local=args.local)
    table = render_table(rows)
    if args.json:
        import json as _json

        print(table, file=sys.stderr)
        for r in rows:
            print(_json.dumps({
                "metric": f"lowered {r.workload}",
                "partitions": r.num_partitions,
                "collectives": r.n_collectives,
                "donated_params": r.donated_params,
                "ok": r.ok,
                "problems": r.problems,
            }))
    else:
        print(table)
    bad = [r for r in rows if not r.ok]
    if bad:
        print(
            "lowered audit FAILED — "
            + ", ".join(r.workload for r in bad),
            file=sys.stderr,
        )
        return 1
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())

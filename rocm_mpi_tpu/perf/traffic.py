"""Compiled HBM-traffic audit: is every distributed step driver
traffic-minimal, *provably*?

The reference's whole performance ladder is judged by one number — T_eff,
effective memory throughput against the ideal of exactly (2+1)
array-traversals per step: read T, write T2, read Cp (BASELINE.md;
/root/reference/scripts/diffusion_2D_perf.jl:55-58). A distributed step
can silently drift away from that bound through staging copies the
schedule never needed — concatenate splices, defensive buffer copies,
re-exchanged loop invariants — and wall-clock timing on a loaded CI box
cannot catch the drift. This module catches it statically:

1. lower + compile each step driver's per-invocation program on the CPU
   backend (the HLO *structure* — staging copies, collective shapes,
   materialized intermediates — is what the audit cares about, and it is
   visible without any accelerator);
2. walk the optimized entry HLO and model its memory traffic per op
   (`hlo_bytes_accessed`): every op reads its operands and writes its
   result, EXCEPT the ops XLA executes without touching the full buffer
   (in-place `dynamic-update-slice` costs two update-sized accesses;
   `slice` reads only what it emits). The raw
   `compiled.cost_analysis()["bytes accessed"]` (via the
   `utils/compat.cost_analysis_dict` chokepoint) is recorded alongside,
   but it charges every in-place ghost write a whole-buffer round trip,
   which would drown the very staging signal the gate watches for — both
   numbers appear in the report;
3. compare against the variant's analytic A_eff ideal (`ideal_*_bytes`:
   the traversal count a traffic-minimal schedule needs, docs/PERF.md)
   and gate the ratio against the committed budget
   (rocm_mpi_tpu/perf/budgets.json).

The audit runs per-shard: programs are compiled over a small multi-device
CPU mesh (the acceptance geometry is 2 virtual ranks) and the modeled
bytes are the per-partition program's. Results are emitted as
`telemetry.annotate("step.traffic", ...)` facts when telemetry is on.
"""

from __future__ import annotations

import dataclasses
import json
import math
import pathlib
import re

DEFAULT_LOCAL = 64
DEFAULT_DEEP_K = 8
BUDGETS_PATH = pathlib.Path(__file__).with_name("budgets.json")

# ---------------------------------------------------------------------------
# The per-op traffic model over optimized HLO text
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}
_SHAPE_RE = re.compile(
    r"\b(pred|s8|u8|s16|u16|bf16|f16|s32|u32|f32|s64|u64|f64|c64|c128)"
    r"\[([0-9,]*)\]"
)
_OP_RE = re.compile(r"^(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*?)\s([\w\-]+)\(")
_NAME_RE = re.compile(r"%([\w.\-]+)")

# Ops that move no tensor bytes of their own (parameters/constants are
# charged where they are consumed, as operand reads).
_FREE_OPS = frozenset({
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id", "custom-call",
})


def _tokens_bytes(text: str) -> list[int]:
    return [
        _DTYPE_BYTES[m.group(1)] * math.prod(
            int(d) for d in m.group(2).split(",") if d
        )
        for m in _SHAPE_RE.finditer(text)
    ]


def _call_args(line: str, start: int) -> str:
    """The text inside the op's parentheses, `start` just past `op(`."""
    depth = 1
    for i in range(start, len(line)):
        c = line[i]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                return line[start:i]
    return line[start:]


def entry_ops(hlo_text: str):
    """Yield (op, result_bytes, operand_bytes) per instruction of the
    optimized entry computation. HLO text (jax 0.9.0) names operands
    without their types (`collective-permute(%wrapped_slice)`), so
    operand shapes resolve through a name -> bytes table built from the
    computation's own definitions, which precede their uses. An operand
    name the table lacks is reported as None."""
    sizes: dict[str, int] = {}
    in_entry = False
    for raw in hlo_text.splitlines():
        line = raw.strip()
        if line.startswith("ENTRY "):
            in_entry = True
            continue
        if in_entry and line.startswith("}"):
            break
        if not in_entry:
            continue
        m = _OP_RE.match(line)
        if not m:
            continue
        name, result_text, op = m.groups()
        result_bytes = sum(_tokens_bytes(result_text))
        sizes[name] = result_bytes
        operands = [
            sizes.get(n) for n in _NAME_RE.findall(_call_args(line, m.end()))
        ]
        yield op, result_bytes, operands


def hlo_wire_bytes(hlo_text: str) -> int:
    """Bytes this partition's program SENDS over collectives per
    invocation: the summed operand bytes of its `collective-permute` ops.
    Unlike the modeled total, this figure is exact and lowering-stable —
    a schedule that re-grows an exchange (the old per-sweep coefficient
    re-exchange) moves it by whole slabs, so the gate holds it to the
    analytic wire ideal with almost no tolerance.

    Raises ValueError when a collective's operand cannot be sized or a
    program with collectives reads 0 bytes: a parse that silently reads
    0 would let every wire row of the gate pass without measuring."""
    total = collectives = 0
    for op, _, operands in entry_ops(hlo_text):
        if op != "collective-permute":
            continue
        collectives += 1
        if not operands or None in operands:
            raise ValueError(
                "collective-permute operand not defined in the entry "
                "computation: HLO text format drift"
            )
        total += sum(operands)
    if collectives and not total:
        raise ValueError(
            f"{collectives} collective-permute op(s) but 0 wire bytes: "
            "HLO text format drift"
        )
    return total


def hlo_bytes_accessed(hlo_text: str) -> int:
    """Modeled memory traffic (bytes) of one invocation of the optimized
    entry computation.

    Per-op rules (the module docstring has the why):
      * default: sum(operand bytes) + result bytes — producers write
        memory, consumers read it back;
      * `fusion`: result bytes + per-operand min(operand, result) bytes —
        fusions stream their boundary I/O (subcomputations live in
        registers), and a fusion that emits a slab never streams more of
        an operand than it emits (a kLoop fusion slicing one column out
        of the padded buffer reads a column, not the buffer);
      * `dynamic-update-slice`: 2 × update bytes (XLA updates in place);
      * `slice` / `dynamic-slice`: 2 × result bytes (reads only the
        window it emits);
      * `collective-permute`: operand + result (send + receive);
      * parameters, constants, tuple plumbing: free (charged at use).

    Raises ValueError on an operand it cannot size, as hlo_wire_bytes
    does: counting it as 0 would loosen every traffic row unseen.
    """
    total = 0
    for op, result_bytes, operands in entry_ops(hlo_text):
        if op in _FREE_OPS:
            continue
        if None in operands:
            raise ValueError(
                f"{op} operand not defined in the entry computation: "
                "HLO text format drift"
            )
        if op == "dynamic-update-slice":
            total += 2 * (operands[1] if len(operands) > 1 else result_bytes)
        elif op in ("slice", "dynamic-slice"):
            total += 2 * result_bytes
        elif op == "fusion":
            total += result_bytes + sum(
                min(t, result_bytes) for t in operands
            )
        else:
            total += sum(operands) + result_bytes
    return total


# ---------------------------------------------------------------------------
# Analytic A_eff ideals (docs/PERF.md)
# ---------------------------------------------------------------------------


def _prod(xs) -> int:
    return math.prod(int(x) for x in xs)


def ideal_exchanged_step_bytes(local_shape, itemsize: int,
                               width: int = 1) -> int:
    """Per-shard ideal of ONE exchanged step (shard and overlap
    schedules): the (2+1)-traversal bound — read T, write T2, read C —
    plus the irreducible exchange machinery: one padded staging buffer
    (written once, read once by the stencil in place of a raw T read)
    and the ghost slices over the wire (read + send + receive + write,
    all slab-sized)."""
    from rocm_mpi_tpu.parallel.halo import exchange_nbytes

    n = _prod(local_shape) * itemsize
    npad = _prod(ln + 2 * width for ln in local_shape) * itemsize
    halo = exchange_nbytes(local_shape, itemsize, width)
    # read T + write Tp + read Tp + read C + write out  +  4 slab passes
    return 3 * n + 2 * npad + 4 * halo


def ideal_deep_sweep_bytes(local_shape, itemsize: int, k: int) -> int:
    """Per-shard ideal of one deep-halo sweep (k steps, one width-k
    exchange, jnp local form): the exchange staging as above, then k
    local steps each bounded by (2+1) traversals of the PADDED block
    (read Tp, read Cm, write the advanced inner box)."""
    from rocm_mpi_tpu.parallel.halo import exchange_nbytes

    n = _prod(local_shape) * itemsize
    npad = _prod(ln + 2 * k for ln in local_shape) * itemsize
    halo = exchange_nbytes(local_shape, itemsize, k)
    return n + npad + 4 * halo + k * 3 * npad


def ideal_wire_bytes(local_shape, itemsize: int, width: int,
                     wire_mode: str = "f32") -> int:
    """Per-mode closed-form wire ideal of one exchange — the wire-bytes
    ladder's row anchor (halo.exchange_nbytes at the mode's on-wire
    itemsize; parallel/wire.py owns the per-mode tables)."""
    from rocm_mpi_tpu.parallel.halo import exchange_nbytes

    return exchange_nbytes(local_shape, itemsize, width,
                           wire_mode=wire_mode)


# ---------------------------------------------------------------------------
# The audit
# ---------------------------------------------------------------------------


WIRE_TOLERANCE = 1.02  # exact metric; tolerance covers rounding only


@dataclasses.dataclass(frozen=True)
class TrafficRow:
    """One audited step program."""

    variant: str
    steps: int  # steps one program invocation advances
    measured_bytes: int  # modeled traffic per invocation (per shard)
    ideal_bytes: int  # analytic A_eff ideal per invocation
    wire_bytes: int  # exact collective send bytes per invocation
    wire_ideal: int  # analytic exchange_nbytes for the schedule
    cost_analysis_bytes: float  # raw XLA cost-analysis figure (context)
    budget: float | None  # committed max measured/ideal ratio

    @property
    def ratio(self) -> float:
        return self.measured_bytes / self.ideal_bytes

    @property
    def wire_ratio(self) -> float:
        return self.wire_bytes / self.wire_ideal if self.wire_ideal else 0.0

    @property
    def wire_ok(self) -> bool:
        return self.wire_ratio <= WIRE_TOLERANCE

    @property
    def ok(self) -> bool:
        return (
            self.budget is None or self.ratio <= self.budget
        ) and self.wire_ok


def _modeled_bytes(jitted, *args) -> tuple[int, int, float]:
    from rocm_mpi_tpu.utils.compat import cost_analysis_dict

    compiled = jitted.lower(*args).compile()
    raw = cost_analysis_dict(compiled).get("bytes accessed", float("nan"))
    text = compiled.as_text()
    return hlo_bytes_accessed(text), hlo_wire_bytes(text), float(raw)


def load_budgets(path=None) -> dict:
    doc = json.loads(pathlib.Path(path or BUDGETS_PATH).read_text())
    if not isinstance(doc, dict) or "budgets" not in doc:
        raise ValueError(f"unrecognized budgets file {path or BUDGETS_PATH}")
    return doc


def _legacy_overlap_step(model):
    """The pre-rework overlap splice, kept as the gate's KNOWN-WASTE
    fixture: per-axis concatenate halo staging, a concatenate tree
    re-assembling the shard from its region updates, and a trailing
    whole-shard Dirichlet `where` over a mask rebuilt in the step. The
    regression test asserts the gate FAILS this program — proof the audit
    detects the staging-copy class it exists for, not just that budgets
    are loose."""
    import jax
    import jax.numpy as jnp

    from rocm_mpi_tpu.ops.diffusion import step_fused_padded
    from rocm_mpi_tpu.parallel.halo import (
        global_boundary_mask,
        neighbor_shift,
    )
    from rocm_mpi_tpu.parallel.overlap import effective_b_width
    from rocm_mpi_tpu.utils.compat import shard_map

    cfg, grid = model.config, model.grid
    local, ndim = grid.local_shape, grid.ndim
    bw = effective_b_width(local, cfg.b_width)
    dt = cfg.jax_dtype(cfg.dt)

    def concat_exchange(u):
        for ax in range(ndim):
            name = grid.axis_names[ax]
            lo = tuple(
                slice(0, 1) if a == ax else slice(None) for a in range(ndim)
            )
            hi = tuple(
                slice(-1, None) if a == ax else slice(None)
                for a in range(ndim)
            )
            ghost_lo = neighbor_shift(u[hi], name, +1)
            ghost_hi = neighbor_shift(u[lo], name, -1)
            u = jnp.concatenate([ghost_lo, u, ghost_hi], axis=ax)
        return u

    def local_step(Tl, Cpl):
        Tp = concat_exchange(Tl)

        def region(bounds):
            pad_idx = tuple(slice(lo, hi + 2) for lo, hi in bounds)
            core_idx = tuple(slice(lo, hi) for lo, hi in bounds)
            return step_fused_padded(
                Tp[pad_idx], Cpl[core_idx], cfg.lam, dt, cfg.spacing
            )

        def build(axis, prefix):
            if axis == ndim:
                return region(prefix)
            n, b = local[axis], bw[axis]
            rest = [(0, local[a]) for a in range(axis + 1, ndim)]
            parts = [region(prefix + [(0, b)] + rest)]
            if n - 2 * b > 0:
                parts.append(build(axis + 1, prefix + [(b, n - b)]))
            parts.append(region(prefix + [(n - b, n)] + rest))
            return jnp.concatenate(parts, axis=axis)

        new = build(0, [])
        return jnp.where(global_boundary_mask(grid), Tl, new)

    def step(T, C):
        return shard_map(
            local_step,
            mesh=grid.mesh,
            in_specs=(grid.spec, grid.spec),
            out_specs=grid.spec,
            check_vma=False,
        )(T, C)

    # Donated like the audited drivers — the fixture's waste is its
    # concatenate staging, which no aliasing can remove.
    return jax.jit(step, donate_argnums=0)


def audit_variants(local: int = DEFAULT_LOCAL, dims=(2, 1),
                   deep_k: int = DEFAULT_DEEP_K, budgets: dict | None = None,
                   include_waste_fixture: bool = False) -> list[TrafficRow]:
    """Compile + audit the distributed diffusion step drivers on the
    current (CPU) backend: the fused shard step, the overlap step, and
    one deep-k sweep (jnp local form — the shapes the CPU backend
    actually lowers; the Pallas forms are TPU-measured, not CPU-modeled).
    f64 keeps every audited program on the pure-XLA path."""
    import jax

    from rocm_mpi_tpu import telemetry
    from rocm_mpi_tpu.config import DiffusionConfig
    from rocm_mpi_tpu.models import HeatDiffusion
    from rocm_mpi_tpu.parallel.deep_halo import make_deep_sweep

    if budgets is None:
        budgets = load_budgets()
    budget_of = budgets.get("budgets", {})

    dims = tuple(int(d) for d in dims)
    cfg = DiffusionConfig(
        global_shape=tuple(local * d for d in dims),
        lengths=(10.0,) * len(dims),
        nt=8, warmup=0, dtype="f64", dims=dims,
        # A REAL overlap decomposition at the audit's shard size: the
        # default (32,4) frame swallows a 64² shard whole (no interior),
        # which would audit a slab-only program no production overlap
        # run executes.
        b_width=(local // 8, local // 8),
    )
    model = HeatDiffusion(cfg)
    itemsize = jax.numpy.dtype(cfg.jax_dtype).itemsize
    local_shape = model.grid.local_shape
    T, Cp = model.init_state()
    shard_ideal = ideal_exchanged_step_bytes(local_shape, itemsize)

    from rocm_mpi_tpu.parallel.halo import exchange_nbytes

    wire_step = exchange_nbytes(local_shape, itemsize, 1)

    rows: list[TrafficRow] = []

    def audit(variant, budget_key, jitted, args, steps, ideal, wire_ideal):
        measured, wire, raw = _modeled_bytes(jitted, *args)
        rows.append(TrafficRow(
            variant=variant, steps=steps, measured_bytes=measured,
            ideal_bytes=ideal, wire_bytes=wire, wire_ideal=wire_ideal,
            cost_analysis_bytes=raw, budget=budget_of.get(budget_key),
        ))

    # donate=True everywhere: the audited programs carry the drivers'
    # steady-state aliasing (their loop carries donate the field), which
    # is what lets XLA run the ghost-write chain in place. Auditing an
    # undonated step would charge every variant a defensive whole-shard
    # copy no driver ever executes.
    for variant, model_variant in (("shard", "shard"), ("overlap", "hide")):
        step, prepare = model.prepared_step_fn(model_variant, donate=True)
        C = prepare(Cp)
        audit(variant, variant, step, (T, C), 1, shard_ideal, wire_step)

    k = min(deep_k, min(local_shape))
    sched = make_deep_sweep(
        model.grid, k, cfg.lam, cfg.jax_dtype(cfg.dt), cfg.spacing,
        local_form="jnp",
    )
    Cm = jax.jit(sched.prepare)(Cp)
    audit(
        f"deep{k}", "deep",
        jax.jit(sched.sweep, donate_argnums=0), (T, Cm), k,
        ideal_deep_sweep_bytes(local_shape, itemsize, k),
        exchange_nbytes(local_shape, itemsize, k),
    )

    if include_waste_fixture:
        # Gated against the SHARD budget: the fixture is a fused shard
        # step rebuilt with the pre-rework concatenate staging — a
        # traffic regression the gate must reject no matter how its
        # wire bytes look.
        audit("concat-splice(fixture)", "shard",
              _legacy_overlap_step(model), (T, Cp), 1, shard_ideal,
              wire_step)

    if telemetry.enabled():
        for r in rows:
            telemetry.annotate(
                "step.traffic", variant=r.variant, steps=r.steps,
                bytes=int(r.measured_bytes), ideal=int(r.ideal_bytes),
                ratio=round(r.ratio, 4), wire=int(r.wire_bytes),
                wire_ideal=int(r.wire_ideal),
                budget=r.budget if r.budget is not None else -1.0,
            )
    return rows


# ---------------------------------------------------------------------------
# The batched-step audit (multi-tenant serving, docs/SERVING.md)
# ---------------------------------------------------------------------------

DEFAULT_BATCH = 2
DEFAULT_BATCH_FIXTURE_WIDTH = 4


def ideal_batched_step_bytes(local_shape, itemsize: int, lanes: int,
                             width: int = 1) -> int:
    """Per-shard ideal of ONE B-lane batched step: exactly `lanes` ×
    the single-lane exchanged-step ideal — batching amortizes the
    PROGRAM, not the bytes, so a batched program that moves more than
    B× the single-lane bytes (per live lane) is shipping padding
    (the bin scheduler's split rule exists to prevent exactly that)."""
    return lanes * ideal_exchanged_step_bytes(local_shape, itemsize, width)


def audit_batched(local: int = DEFAULT_LOCAL, dims=(2, 1),
                  batch: int = DEFAULT_BATCH,
                  budgets: dict | None = None,
                  include_batch_fixture: bool = False) -> list[TrafficRow]:
    """Compile + audit the B-lane batched diffusion step (the serving
    layer's program class: shard_map over the space×batch mesh, the
    per-lane body vmapped — models.diffusion.batched_step_fn) on the
    current (CPU) backend: modeled bytes/invocation must stay within
    BATCH_TOLERANCE × B × the single-lane ideal, and the collective
    wire bytes must be EXACTLY B × the single-lane exchange (a batched
    exchange that ships more is permuting padding).

    `include_batch_fixture` appends the doctored over-padded row: a
    width-{DEFAULT_BATCH_FIXTURE_WIDTH} program carrying ONE live lane,
    audited against the single live lane's ideal — the padding-inflation
    class the bin scheduler's occupancy floor exists to split away. It
    must fail (the gate exits 1)."""
    import jax
    import numpy as np

    from rocm_mpi_tpu import telemetry
    from rocm_mpi_tpu.config import DiffusionConfig
    from rocm_mpi_tpu.models import HeatDiffusion
    from rocm_mpi_tpu.parallel.halo import exchange_nbytes

    if budgets is None:
        budgets = load_budgets()
    serving = budgets.get("serving", {})
    tolerance = serving.get("batch_tolerance")
    hide_tolerance = serving.get("hide_tolerance")

    dims = tuple(int(d) for d in dims)
    cfg = DiffusionConfig(
        global_shape=tuple(local * d for d in dims),
        lengths=(10.0,) * len(dims),
        nt=8, warmup=0, dtype="f64", dims=dims,
    )
    model = HeatDiffusion(cfg)
    # The batched-hide twin: same problem, a REAL overlap decomposition
    # at the audit's shard size (audit_variants has the why — the
    # default frame would swallow the shard whole).
    model_hide = HeatDiffusion(dataclasses.replace(
        cfg, b_width=(local // 8, local // 8)
    ))
    itemsize = jax.numpy.dtype(cfg.jax_dtype).itemsize
    local_shape = model.grid.local_shape
    wire1 = exchange_nbytes(local_shape, itemsize, 1)
    T0, Cp = model.init_state()
    T0n, Cpn = np.asarray(T0), np.asarray(Cp)

    def measure(width: int, variant: str = "shard", m=None):
        m = model if m is None else m
        bgrid = m.make_batched_grid(width, batch_dims=1)
        step = m.batched_step_fn(bgrid, variant=variant, donate=True)
        Tb = jax.device_put(np.stack([T0n] * width), bgrid.sharding)
        Cb = m.batched_prepare_fn(bgrid, variant)(
            jax.device_put(Cpn, bgrid.aux_sharding)
        )
        return _modeled_bytes(step, Tb, Cb)

    rows: list[TrafficRow] = []
    measured, wire, raw = measure(batch)
    rows.append(TrafficRow(
        variant=f"batched{batch}", steps=1,
        measured_bytes=measured,
        ideal_bytes=ideal_batched_step_bytes(local_shape, itemsize, batch),
        wire_bytes=wire, wire_ideal=batch * wire1,
        cost_analysis_bytes=raw, budget=tolerance,
    ))

    # The batched-hide program (docs/SERVING.md "The pipeline"): the
    # lane-batched comm/compute overlap the serving layer compiles for
    # variant "hide" bins. Its wire bytes must still be EXACTLY B× one
    # lane's exchange (an over-wire batched hide is permuting padding),
    # and its modeled bytes gate against the committed hide tolerance —
    # an un-overlapped or padding-bloated pipeline program fails here,
    # in the lint stage, before it ever serves traffic.
    measured, wire, raw = measure(batch, variant="hide", m=model_hide)
    rows.append(TrafficRow(
        variant=f"batched-hide{batch}", steps=1,
        measured_bytes=measured,
        ideal_bytes=ideal_batched_step_bytes(local_shape, itemsize, batch),
        wire_bytes=wire, wire_ideal=batch * wire1,
        cost_analysis_bytes=raw, budget=hide_tolerance,
    ))

    # The ladder row (docs/SERVING.md "Continuous batching"): the
    # rung-shaped ladder program carrying lanes whose ORIGINAL domains
    # sit 2 cells shy of the rung per axis — the padding class the
    # shape-padding ladder deliberately admits to consolidate program
    # classes. Audited against the ORIGINAL domains' live-cell ideal,
    # so the ratio prices the padded cells the rung ships on top of the
    # batched program's slack; the budget is batch_tolerance × (1 +
    # padded_flops_tolerance) — a rung whose embedding inflates FLOPs
    # past the committed tolerance fails here, the same split rule
    # serving/bins.ladder_shape enforces at admission. The row runs the
    # f32 program class because that is the only one the service admits
    # to the ladder (lossless f32 wire is an eligibility rule). Measured
    # 2.04 on the gate geometry (126×62 → 128×64 rung, 4.9% cell
    # inflation) vs the 3.0 budget.
    pf_tol = serving.get("padded_flops_tolerance")
    if pf_tol is not None:
        from rocm_mpi_tpu.serving.bins import ladder_shape

        orig = tuple(s - 2 for s in cfg.global_shape)
        rung = ladder_shape(orig, tolerance=float(pf_tol))
        cfg_l = dataclasses.replace(cfg, global_shape=rung, dtype="f32")
        model_l = HeatDiffusion(cfg_l)
        ty = cfg_l.jax_dtype
        item_l = jax.numpy.dtype(ty).itemsize
        bgrid = model_l.make_batched_grid(batch, batch_dims=1)
        step = jax.jit(model_l.batched_ladder_step_fn(bgrid),
                       donate_argnums=0)
        Tb = jax.device_put(
            np.zeros((batch,) + rung, ty), bgrid.sharding)
        Cb = jax.device_put(np.ones(rung, ty), bgrid.aux_sharding)
        hold = jax.device_put(
            np.zeros((batch,) + rung, bool), bgrid.sharding)
        dtlam = jax.device_put(np.ones(batch, ty), bgrid.batch_sharding)
        invd2 = tuple(
            jax.device_put(np.ones(batch, ty), bgrid.batch_sharding)
            for _ in range(len(rung)))
        measured, wire, raw = _modeled_bytes(
            step, Tb, Cb, hold, dtlam, *invd2)
        orig_local = tuple(o // d for o, d in zip(orig, dims))
        rows.append(TrafficRow(
            variant=f"ladder{batch}", steps=1,
            measured_bytes=measured,
            ideal_bytes=ideal_batched_step_bytes(
                orig_local, item_l, batch),
            wire_bytes=wire,
            wire_ideal=batch * exchange_nbytes(
                model_l.grid.local_shape, item_l, 1),
            cost_analysis_bytes=raw,
            budget=(None if tolerance is None
                    else float(tolerance) * (1.0 + float(pf_tol))),
        ))

    if include_batch_fixture:
        # The doctored row: a 4-wide program with ONE live lane — the
        # machine executes 4 lanes of bytes for 1 lane of work. Audited
        # per LIVE lane it lands ~4× over; the gate must exit 1.
        w = DEFAULT_BATCH_FIXTURE_WIDTH
        measured, wire, raw = measure(w)
        rows.append(TrafficRow(
            variant=f"batched-pad{w}/1(fixture)", steps=1,
            measured_bytes=measured,
            ideal_bytes=ideal_batched_step_bytes(local_shape, itemsize, 1),
            wire_bytes=wire, wire_ideal=w * wire1,
            cost_analysis_bytes=raw, budget=tolerance,
        ))

    if telemetry.enabled():
        for r in rows:
            telemetry.annotate(
                "step.traffic", variant=r.variant, steps=r.steps,
                bytes=int(r.measured_bytes), ideal=int(r.ideal_bytes),
                ratio=round(r.ratio, 4), wire=int(r.wire_bytes),
                wire_ideal=int(r.wire_ideal),
                budget=r.budget if r.budget is not None else -1.0,
            )
    return rows


# ---------------------------------------------------------------------------
# The wire-bytes ladder (per-mode reduced-precision exchange audit)
# ---------------------------------------------------------------------------

DEFAULT_WIRE_LOCAL = 64
DEFAULT_WIRE_DEEP_K = 4


@dataclasses.dataclass(frozen=True)
class WireRow:
    """One wire mode's audited deep-sweep program: its EXACT collective
    send bytes from the optimized HLO, held against two anchors — the
    mode's own closed-form ideal (the program must not ship more than
    the codec's accounting, WIRE_TOLERANCE) and the committed ladder
    row (the fraction of the full-precision wire this mode is allowed
    to ship; rocm_mpi_tpu/perf/budgets.json "wire")."""

    mode: str
    wire_bytes: int  # measured send bytes per sweep (per shard)
    full_ideal: int  # full-precision (f32) closed-form wire bytes
    mode_ideal: int  # this mode's closed-form wire bytes
    ladder: float | None  # committed max wire_bytes/full_ideal fraction
    fixture: bool = False  # the doctored over-ladder regression row

    @property
    def fraction(self) -> float:
        return self.wire_bytes / self.full_ideal if self.full_ideal else 0.0

    @property
    def ok(self) -> bool:
        under_ladder = (
            self.ladder is None or self.fraction <= self.ladder
        )
        exact = self.wire_bytes <= WIRE_TOLERANCE * self.mode_ideal
        return under_ladder and exact


def audit_wire_modes(local: int = DEFAULT_WIRE_LOCAL, dims=(2, 1),
                     deep_k: int = DEFAULT_WIRE_DEEP_K,
                     budgets: dict | None = None,
                     include_wire_fixture: bool = False) -> list[WireRow]:
    """Compile the deep-halo sweep (jnp local form, f32 state — the one
    schedule every wire mode supports, stateful modes included) once per
    wire mode on the current (CPU) backend and measure its EXACT
    collective send bytes from the optimized HLO. Each row must land
    within WIRE_TOLERANCE of the mode's closed-form ideal AND under the
    committed ladder fraction of the full-precision wire — the proof
    that a bf16 exchange really ships half the bytes (and the int8/delta
    modes strictly less), not just that a flag flipped.

    `include_wire_fixture` appends the doctored regression row: a
    program that SHIPS full-precision slabs audited against the bf16
    ladder row — the drift class the ladder exists to catch (a codec
    edit that silently stops downcasting). It must fail."""
    import jax
    import jax.numpy as jnp

    from rocm_mpi_tpu import telemetry
    from rocm_mpi_tpu.config import DiffusionConfig
    from rocm_mpi_tpu.models import HeatDiffusion
    from rocm_mpi_tpu.parallel import wire
    from rocm_mpi_tpu.parallel.deep_halo import make_deep_sweep

    if budgets is None:
        budgets = load_budgets()
    wire_cfg = budgets.get("wire", {})
    ladder_of = wire_cfg.get("ladder", dict(wire.DEFAULT_LADDER))

    dims = tuple(int(d) for d in dims)
    cfg = DiffusionConfig(
        global_shape=tuple(local * d for d in dims),
        lengths=(10.0,) * len(dims),
        nt=8, warmup=0, dtype="f32", dims=dims,
    )
    model = HeatDiffusion(cfg)
    local_shape = model.grid.local_shape
    k = min(int(deep_k), min(local_shape))
    itemsize = 4  # f32 state — the production wire-plane dtype
    full_ideal = ideal_wire_bytes(local_shape, itemsize, k, "f32")
    T, Cp = model.init_state()
    dt = cfg.jax_dtype(cfg.dt)

    def measure(mode: str) -> int:
        sched = make_deep_sweep(model.grid, k, cfg.lam, dt, cfg.spacing,
                                local_form="jnp", wire_mode=mode)
        Cm = jax.jit(sched.prepare)(Cp)
        jitted = jax.jit(sched.sweep, donate_argnums=0)
        args = (T, Cm) if sched.init_wire is None else (
            T, Cm, sched.init_wire(jnp.float32)
        )
        text = jitted.lower(*args).compile().as_text()
        return hlo_wire_bytes(text)

    rows: list[WireRow] = []
    for mode in wire.WIRE_MODES:
        rows.append(WireRow(
            mode=mode,
            wire_bytes=measure(mode),
            full_ideal=full_ideal,
            mode_ideal=ideal_wire_bytes(local_shape, itemsize, k, mode),
            ladder=ladder_of.get(mode),
        ))

    if include_wire_fixture:
        # The doctored row: a full-precision sweep claiming the bf16
        # ladder row. fraction 1.0 > 0.55 — the gate must exit 1.
        rows.append(WireRow(
            mode="bf16(fixture)",
            wire_bytes=measure("f32"),
            full_ideal=full_ideal,
            mode_ideal=ideal_wire_bytes(local_shape, itemsize, k, "bf16"),
            ladder=ladder_of.get("bf16"),
            fixture=True,
        ))

    if telemetry.enabled():
        for r in rows:
            telemetry.annotate(
                "wire.ladder", mode=r.mode, bytes=int(r.wire_bytes),
                full_ideal=int(r.full_ideal),
                mode_ideal=int(r.mode_ideal),
                fraction=round(r.fraction, 4),
                ladder=r.ladder if r.ladder is not None else -1.0,
            )
    return rows


def render_wire_table(rows: list[WireRow]) -> str:
    head = (
        f"{'wire mode':16s} {'wire/sweep':>10s} {'f32 ideal':>10s} "
        f"{'mode ideal':>10s} {'frac':>6s} {'ladder':>6s} status"
    )
    lines = [head, "-" * len(head)]
    for r in rows:
        ladder = f"{r.ladder:.2f}" if r.ladder is not None else "   —"
        if r.ok:
            status = "ok"
        elif r.ladder is not None and r.fraction > r.ladder:
            status = "OVER LADDER"
        else:
            status = "OVER MODE IDEAL"
        lines.append(
            f"{r.mode:16s} {r.wire_bytes:10d} {r.full_ideal:10d} "
            f"{r.mode_ideal:10d} {r.fraction:6.3f} {ladder:>6s} {status}"
        )
    return "\n".join(lines)


def render_table(rows: list[TrafficRow]) -> str:
    head = (
        f"{'variant':24s} {'steps':>5s} {'bytes/invoc':>12s} "
        f"{'ideal':>12s} {'ratio':>6s} {'budget':>6s} "
        f"{'wire':>8s} {'wire0':>8s} {'xla-ca':>12s} status"
    )
    lines = [head, "-" * len(head)]
    for r in rows:
        budget = f"{r.budget:.2f}" if r.budget is not None else "   —"
        if r.ok:
            status = "ok"
        elif not r.wire_ok:
            status = "WIRE OVER IDEAL"
        else:
            status = "OVER BUDGET"
        lines.append(
            f"{r.variant:24s} {r.steps:5d} {r.measured_bytes:12d} "
            f"{r.ideal_bytes:12d} {r.ratio:6.2f} {budget:>6s} "
            f"{r.wire_bytes:8d} {r.wire_ideal:8d} "
            f"{r.cost_analysis_bytes:12.0f} {status}"
        )
    return "\n".join(lines)

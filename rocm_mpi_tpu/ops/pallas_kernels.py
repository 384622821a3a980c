"""Pallas TPU stencil kernels (D7) — the hand-tuned rungs of the ladder.

TPU-native re-design of the reference's hand-written GPU kernels:

* `fused_step_padded` — the fused memory-bound diffusion kernel
  (/root/reference/scripts/diffusion_2D_perf.jl:3-13). Whole-block-in-VMEM
  for shard sizes that fit (the 252²/chip benchmark regime: the entire field
  lives on-chip), row-striped with a 3-slot neighbor-block trick for large
  single-chip grids (the 12288² regime), pipelining HBM→VMEM stripe loads
  against VPU compute.
* `fused_multi_step` — a TPU-only optimization with no reference analog:
  when the whole field fits in VMEM, run the *entire time loop inside one
  kernel*, never spilling T to HBM between steps. The reference pays 3
  whole-array HBM passes per step by construction; on TPU the memory-bound
  assumption dissolves for VMEM-resident fields.

The `gridsize`-is-workitems convention of `@roc` does not carry over: Pallas
grids count *blocks* (SURVEY.md §7 hard-parts note). The reference's
`threads=(32,8)` tuning knob maps to the stripe height `tm` here.

f64 note: Mosaic (the TPU Pallas compiler) does not support f64; the f64
parity path uses these kernels in interpreter mode (tests) or the jnp
step functions (production), per SURVEY.md §7 "f64 on TPU".
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from rocm_mpi_tpu.utils.compat import pallas as pl
from rocm_mpi_tpu.utils.compat import pallas_tpu as pltpu

# Whole-block kernels hold ~5 block-sized buffers in VMEM; stay well under
# the ~16 MB/core budget (pallas_guide.md "Memory Hierarchy").
_VMEM_BLOCK_BUDGET_BYTES = 2 * 1024 * 1024
# Largest in-kernel working slab ((tm+2g) rows) the per-step striped kernel
# may assemble: beyond this the pipeline buffers + lap temporaries blow the
# VMEM compile boundary (measured on v5e, see masked_step's tm selection).
_PS_SLAB_BUDGET_BYTES = 2_500_000


def _supports_compiled(dtype) -> bool:
    return jnp.dtype(dtype).itemsize <= 4


def _upcast_for_compute(*arrays):
    """bf16 is STORAGE-ONLY in this kernel family (r4): operands are upcast
    to f32 on entry and results rounded back once at the kernel boundary.
    bf16 keeps its whole value — halved HBM/VMEM traffic — while the step
    arithmetic runs at f32, so quantization is injected once per kernel
    (per step for the per-step kernels, per chunk/sweep for the multi-step
    ones) instead of compounding through every intermediate. Measured
    motivation: with per-step bf16 rounding the 252² trajectory freezes
    (updates quantize to zero; docs/bf16_error_cpu252_perstep_r4.txt vs
    the flat curve of docs/bf16_error_cpu252_vmem_r4.txt)."""
    if arrays[0].dtype == jnp.bfloat16:
        return tuple(a.astype(jnp.float32) for a in arrays)
    return arrays


def _compute_itemsize(dtype) -> int:
    """In-kernel bytes per element: bf16 state is upcast to f32 inside
    the kernels (_upcast_for_compute), so every VMEM/admission/stripe
    policy must budget at >= f32 width, not storage width. The ONE place
    the storage-only width rule lives."""
    return max(jnp.dtype(dtype).itemsize, 4)


def _tiled_elems(shape) -> int:
    """Elements a block of `shape` occupies in VMEM: Mosaic lays the last
    two axes out in (8 sublane x 128 lane) tiles, so a 4-column hide slab
    fills 128 lanes per row. Budgeting the logical size instead let a
    12224x4 slab take the whole-block kernel and overrun scoped VMEM by
    6.5 MB on v5e (tests/test_tpu_compile.py)."""
    dims = list(shape)
    if dims:
        dims[-1] = -(-dims[-1] // 128) * 128
    if len(dims) >= 2:
        dims[-2] = -(-dims[-2] // 8) * 8
    return math.prod(dims)


def _compute_nbytes(arr) -> int:
    """In-kernel working-set bytes per field (see _compute_itemsize).
    Logical size: the VMEM-resident admission and chunk-class policies
    were measured against it."""
    return arr.size * _compute_itemsize(arr.dtype)


def _block_nbytes(arr) -> int:
    """Bytes one whole-block operand takes in VMEM as tiled (see
    _tiled_elems): the whole-block vs striped choice of the per-step
    kernels, where the logical size let a thin slab overrun."""
    return _tiled_elems(arr.shape) * _compute_itemsize(arr.dtype)


def _out_struct(shape, exemplar):
    """ShapeDtypeStruct matching `exemplar`'s dtype and mesh-varying axes.

    Inside shard_map (jax>=0.9 check_vma), pallas_call outputs must declare
    which mesh axes they vary over; propagate the input's vma set
    (version-portably — utils.compat owns the jax-API drift).
    """
    from rocm_mpi_tpu.utils.compat import out_struct_like

    return out_struct_like(shape, exemplar)


def _interpret_default() -> bool:
    """Dispatch policy when the caller passes interpret=None: compiled
    Mosaic on TPU, the Pallas interpreter on CPU (the test harness).
    Any OTHER accelerator backend raises — silently interpreting on a GPU
    would run ≈hours instead of surfacing 'this framework's kernels are
    TPU-native' (VERDICT r3 hygiene note)."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"no default Pallas dispatch for backend {backend!r}: compiled "
        "Mosaic kernels are TPU-only, and the interpreter (the CPU test "
        "path) would silently be hours-slow on an accelerator; pass "
        "interpret= explicitly to override"
    )


def _lap_from_padded(Tp, inv_d2):
    """Σ_ax (hi - 2·c + lo)/dx² from a width-1-padded block (5/7-point)."""
    ndim = Tp.ndim
    core = tuple(slice(1, -1) for _ in range(ndim))
    lap = None
    for ax in range(ndim):
        hi = tuple(slice(2, None) if a == ax else slice(1, -1) for a in range(ndim))
        lo = tuple(slice(None, -2) if a == ax else slice(1, -1) for a in range(ndim))
        term = (Tp[hi] - 2.0 * Tp[core] + Tp[lo]) * inv_d2[ax]
        lap = term if lap is None else lap + term
    return lap


# ---------------------------------------------------------------------------
# Whole-block kernel: core update from a padded block (shard fits in VMEM).
# ---------------------------------------------------------------------------


def _fused_kernel_whole(Tp_ref, Cp_ref, out_ref, *, lam, dt, inv_d2):
    Tp, Cp = _upcast_for_compute(Tp_ref[:], Cp_ref[:])
    core = tuple(slice(1, -1) for _ in range(Tp.ndim))
    out_ref[:] = (
        Tp[core] + (dt * lam) / Cp * _lap_from_padded(Tp, inv_d2)
    ).astype(out_ref.dtype)


def fused_step_padded(Tp, Cp, lam, dt, spacing, interpret=None):
    """Candidate update for every core cell given the padded block `Tp`.

    Pallas counterpart of ops.diffusion.step_fused_padded (same contract:
    caller supplies ghosts via halo.exchange_halo and masks global-boundary
    cells). Dispatches whole-block vs row-striped by VMEM footprint.
    """
    if interpret is None:
        interpret = _interpret_default()
    if not _supports_compiled(Tp.dtype) and not interpret:
        raise TypeError(
            f"Mosaic does not support {Tp.dtype}; use the jnp path or "
            "interpret mode for f64 parity runs"
        )
    # Bake scalars into the kernel as Python floats (captured jnp scalars
    # are rejected by pallas_call; physics constants are static anyway).
    lam, dt = float(lam), float(dt)
    inv_d2 = tuple(1.0 / (float(d) * float(d)) for d in spacing)
    nbytes = _block_nbytes(Cp)
    if Tp.ndim in (2, 3) and nbytes > _VMEM_BLOCK_BUDGET_BYTES:
        return _fused_step_striped(Tp, Cp, lam, dt, inv_d2, interpret)
    kernel = functools.partial(
        _fused_kernel_whole, lam=lam, dt=dt, inv_d2=inv_d2
    )
    return pl.pallas_call(
        kernel,
        out_shape=_out_struct(Cp.shape, Cp),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        interpret=interpret,
    )(Tp, Cp)


# ---------------------------------------------------------------------------
# Row-striped kernel for large 2D grids: 3-slot neighbor-block trick.
# Output stripe i (tm rows of the core) reads padded rows [i·tm, i·tm+tm+2),
# assembled from padded row-blocks i and i+1 — overlapping windows built
# from non-overlapping BlockSpecs.
# ---------------------------------------------------------------------------


def _fused_kernel_striped(Ta_ref, Tb_ref, Cp_ref, out_ref, *, lam, dt, inv_d2):
    Ta = Ta_ref[:]  # padded rows [i·tm, i·tm+tm)
    Tb = Tb_ref[:]  # padded rows [i·tm+tm, i·tm+2·tm); last block is partial
    # `ext` is a fully padded block for this output stripe: padded along
    # axis 0 by the stripe overlap, along the rest by Tp's own pad ring.
    ext = jnp.concatenate([Ta, Tb[:2]], axis=0)  # rows [i·tm, i·tm+tm+2)
    ext, Cp = _upcast_for_compute(ext, Cp_ref[:])
    core = tuple(slice(1, -1) for _ in range(ext.ndim))
    out_ref[:] = (
        ext[core] + (dt * lam) / Cp * _lap_from_padded(ext, inv_d2)
    ).astype(out_ref.dtype)


def _stripe_height(row_bytes: int) -> int:
    """Stripe height for the striped kernels: sized so one stripe
    (`row_bytes` bytes per padded row) fits the per-buffer VMEM budget
    (the striped kernel holds ~4 block operands, each double-buffered by
    the Pallas pipeline — hence budget/2 per buffer), rounded down to the
    f32 sublane tile (8). The analog of the reference's `threads=(32,8)`
    tile knob (perf.jl:23), chosen automatically.

    No divisibility constraint on the row count: the grid is
    ceil-divided and Pallas masks partial trailing blocks (out-of-range
    reads feed only dropped rows; out-of-range writes are dropped) —
    pad-to-tile without materializing any padding.
    """
    per_buffer = _VMEM_BLOCK_BUDGET_BYTES // 2
    return max(8, (per_buffer // max(1, row_bytes)) // 8 * 8)


def _striped_call(kernel, Tp, C, interpret):
    """Shared launch of the 3-slot striped kernels over ceil(n1/tm) stripes.

    Output stripe i (tm core rows) reads padded rows [i·tm, i·tm+tm+2),
    assembled in-kernel from padded row-blocks i and i+1 — overlapping
    windows built from non-overlapping BlockSpecs. `C` is the core-shaped
    coefficient operand (Cp or Cm). Partial-stripe bookkeeping:
      - last output stripe may be partial → Pallas drops OOB writes;
      - block i+1 may be partly or wholly OOB on Tp → its index is clamped
        and the garbage rows feed only dropped output rows (when the core
        row count is ≤ tm-2 past the last full stripe, every needed padded
        row is already inside block i; otherwise row n1+1 exists in Tp).
    """
    core = C.shape  # Tp is core + 2 per axis
    n1, rest = core[0], core[1:]
    rest_p = tuple(n + 2 for n in rest)
    # bf16 operands are upcast to f32 in-kernel: size stripes at f32 width.
    row_bytes = _compute_itemsize(C.dtype) * _tiled_elems(rest_p)
    tm = _stripe_height(row_bytes)
    grid = (-(-n1 // tm),)
    zeros = (0,) * len(rest)
    return pl.pallas_call(
        kernel,
        out_shape=_out_struct(core, C),
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (tm,) + rest_p, lambda i: (i,) + zeros, memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (tm,) + rest_p,
                lambda i: (i + 1,) + zeros,
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (tm,) + rest, lambda i: (i,) + zeros, memory_space=pltpu.VMEM
            ),
        ],
        out_specs=pl.BlockSpec(
            (tm,) + rest, lambda i: (i,) + zeros, memory_space=pltpu.VMEM
        ),
        interpret=interpret,
    )(Tp, Tp, C)


def _fused_step_striped(Tp, Cp, lam, dt, inv_d2, interpret):
    kernel = functools.partial(
        _fused_kernel_striped, lam=lam, dt=dt, inv_d2=inv_d2
    )
    return _striped_call(kernel, Tp, Cp, interpret)


# ---------------------------------------------------------------------------
# Cm-masked per-step kernels: the Dirichlet mask and the dt·λ/Cp divide are
# folded into a precomputed coefficient Cm (edge_masked_cm / the sharded
# boundary-masked equivalent), computed ONCE per run instead of per step —
# one kernel per step replaces the reference-parity path's
# kernel + divide + where-mask op chain.
# ---------------------------------------------------------------------------


def _fused_kernel_whole_cm(Tp_ref, Cm_ref, out_ref, *, inv_d2):
    Tp, Cm = _upcast_for_compute(Tp_ref[:], Cm_ref[:])
    core = tuple(slice(1, -1) for _ in range(Tp.ndim))
    out_ref[:] = (
        Tp[core] + Cm * _lap_from_padded(Tp, inv_d2)
    ).astype(out_ref.dtype)


def _fused_kernel_striped_cm(Ta_ref, Tb_ref, Cm_ref, out_ref, *, inv_d2):
    ext = jnp.concatenate([Ta_ref[:], Tb_ref[:2]], axis=0)
    ext, Cm = _upcast_for_compute(ext, Cm_ref[:])
    core = tuple(slice(1, -1) for _ in range(ext.ndim))
    out_ref[:] = (
        ext[core] + Cm * _lap_from_padded(ext, inv_d2)
    ).astype(out_ref.dtype)


def fused_step_cm(Tp, Cm, spacing, interpret=None):
    """Masked per-step core update: new = Tp[core] + Cm · ∇²(Tp).

    `Tp` is the width-1-padded block (ghosts from exchange_halo); `Cm` is
    the core-shaped masked coefficient — (dt·λ)/Cp where the cell updates,
    exactly 0.0 where it is held fixed (global Dirichlet boundary). Because
    the mask is data, the Dirichlet `where` of the unmasked contract
    disappears and one Pallas program serves the whole step (the fused
    memory-bound kernel of diffusion_2D_perf.jl:3-13, with its `ix>1 && …`
    guard carried by Cm instead of control flow). Whole-block in VMEM when
    the shard fits, 3-slot striped otherwise.
    """
    if interpret is None:
        interpret = _interpret_default()
    if not _supports_compiled(Tp.dtype) and not interpret:
        raise TypeError(
            f"Mosaic does not support {Tp.dtype}; use the jnp path or "
            "interpret mode for f64 parity runs"
        )
    inv_d2 = tuple(1.0 / (float(d) * float(d)) for d in spacing)
    nbytes = _block_nbytes(Cm)
    if Tp.ndim in (2, 3) and nbytes > _VMEM_BLOCK_BUDGET_BYTES:
        kernel = functools.partial(_fused_kernel_striped_cm, inv_d2=inv_d2)
        return _striped_call(kernel, Tp, Cm, interpret)
    return pl.pallas_call(
        functools.partial(_fused_kernel_whole_cm, inv_d2=inv_d2),
        out_shape=_out_struct(Cm.shape, Cm),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        interpret=interpret,
    )(Tp, Cm)


# ---------------------------------------------------------------------------
# kp rung: three separate kernels with staggered-grid shapes — the
# kernel-programming teaching ladder of the reference
# (/root/reference/scripts/diffusion_2D_kp.jl: Flux! :16-26, Residual!
# :33-40, Update! :47-54), with the same staggered shapes qx=(nx-1,ny-2),
# qy=(nx-2,ny-1), dTdt=(nx-2,ny-2) (scripts/diffusion_2D_ap.jl:22-24).
# Expressed against a width-1-padded block so the same contract serves
# single-device and shard_map use. Whole-array VMEM kernels: the kp rung
# runs 128²-class grids (kp.jl:62); its point is pedagogy and the
# 3-sync-per-step cost the fused rung removes, not scale.
# ---------------------------------------------------------------------------


def _flux_kernel(Tp_ref, qx_ref, qy_ref, *, lam, inv_d):
    # Fourier's law on the staggered grid: q = -λ ∂T (kp.jl Flux!).
    (Tp,) = _upcast_for_compute(Tp_ref[:])
    qx_ref[:] = (-lam * (Tp[1:, 1:-1] - Tp[:-1, 1:-1]) * inv_d[0]).astype(
        qx_ref.dtype
    )
    qy_ref[:] = (-lam * (Tp[1:-1, 1:] - Tp[1:-1, :-1]) * inv_d[1]).astype(
        qy_ref.dtype
    )


def _residual_kernel(qx_ref, qy_ref, Cp_ref, dTdt_ref, *, inv_d):
    # Conservation of energy: ∂T/∂t = 1/cₚ(-∇·q) (kp.jl Residual!).
    qx, qy, Cp = _upcast_for_compute(qx_ref[:], qy_ref[:], Cp_ref[:])
    div = (qx[1:, :] - qx[:-1, :]) * inv_d[0] + (
        qy[:, 1:] - qy[:, :-1]
    ) * inv_d[1]
    dTdt_ref[:] = (-div / Cp).astype(dTdt_ref.dtype)


def _update_kernel(Tp_ref, dTdt_ref, out_ref, *, dt):
    # Temperature update: T_new = T_old + dt·∂T/∂t (kp.jl Update!).
    Tp, dTdt = _upcast_for_compute(Tp_ref[:], dTdt_ref[:])
    out_ref[:] = (Tp[1:-1, 1:-1] + dt * dTdt).astype(out_ref.dtype)


def kp_step_padded(Tp, Cp, lam, dt, spacing, interpret=None):
    """Candidate core update via the 3-kernel ladder (kp variant).

    Same contract as fused_step_padded but as three separate device
    programs per step — reproducing the reference kp rung's structure
    (three launches + three syncs, kp.jl:87-92) to make the fused rung's
    win measurable.
    """
    if Cp.ndim != 2:
        raise ValueError(
            "the kp ladder rung is 2D-only (as is the reference's kp app); "
            "use variants 'perf'/'hide' for 3D grids"
        )
    if interpret is None:
        interpret = _interpret_default()
    if not _supports_compiled(Tp.dtype) and not interpret:
        raise TypeError(f"Mosaic does not support {Tp.dtype}")
    lam, dt = float(lam), float(dt)
    inv_d = tuple(1.0 / float(d) for d in spacing)
    lx, ly = Cp.shape  # core shape; Tp is (lx+2, ly+2)

    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    qx, qy = pl.pallas_call(
        functools.partial(_flux_kernel, lam=lam, inv_d=inv_d),
        out_shape=(
            _out_struct((lx + 1, ly), Tp),
            _out_struct((lx, ly + 1), Tp),
        ),
        in_specs=[vmem],
        out_specs=(vmem, vmem),
        interpret=interpret,
    )(Tp)
    dTdt = pl.pallas_call(
        functools.partial(_residual_kernel, inv_d=inv_d),
        out_shape=_out_struct((lx, ly), Cp),
        in_specs=[vmem, vmem, vmem],
        out_specs=vmem,
        interpret=interpret,
    )(qx, qy, Cp)
    return pl.pallas_call(
        functools.partial(_update_kernel, dt=dt),
        out_shape=_out_struct((lx, ly), Cp),
        in_specs=[vmem, vmem],
        out_specs=vmem,
        interpret=interpret,
    )(Tp, dTdt)


# ---------------------------------------------------------------------------
# Whole-loop-in-VMEM kernel: nt steps without touching HBM (single shard).
# ---------------------------------------------------------------------------


# Equal-spacing body form for _multi_step_kernel: "eqc" (A∘T + c∘s, the
# r3-measured production form) or "conly" (A-free, one fewer VMEM operand
# stream). A module constant, not config plumbing: the choice is a
# measured hardware default, not a user decision — flip it here when the
# chip A/B (scripts/bench_kernel_forms.py, VERDICT r4 next #2) justifies.
EQC_BODY_FORM = "eqc"

# Pad the VMEM-resident loop's field to power-of-two axes (252² → 256²):
# every vreg tile is then full and the ±1 rolls are aligned shifts. The
# pad ring carries Cm = 0, so pad cells never update and the interior is
# bit-identical to the unpadded program (wraparound only ever reaches
# frozen cells — the kernel's own Dirichlet argument). Same contract as
# EQC_BODY_FORM: a measured hardware default, flipped here if the chip
# A/B's pad_eqc/pad_conly rows justify; the CPU bitwise-equivalence test
# (tests/test_pallas_kernels.py) holds either way.
VMEM_PAD_POW2 = False

class KernelChoice(NamedTuple):
    """What a kernel entry point decided at trace time — dispatch route,
    effective chunk/body form, and the pad outcome — as an explicit
    record instead of a post-hoc module-global query flag (the retired
    `last_pad_applied` pattern: a global written at trace time is stale
    the moment a cached program is reused; a record computed by the pure
    planner is valid whenever it is recomputed). `plan_vmem_loop` is the
    planner; bench.py labels its ladder rungs from this, and the
    autotuner keys measured programs by it."""

    op: str  # the tuning-op spelling ("diffusion.vmem_loop", …)
    dispatch: str  # "vmem-loop" | "whole" | "striped" | …
    chunk: int | None = None  # effective steps per kernel launch
    body_form: str | None = None  # resolved eqc/conly (vmem loop)
    pad_requested: bool = False
    # pad outcome, the old last_pad_applied tri-state: True = applied,
    # False = requested but skipped (VMEM budget), None = not requested
    # or nothing to pad (already pow2).
    pad_applied: bool | None = None
    padded_shape: tuple | None = None  # set only when pad_applied


# Deprecation shim state for last_pad_applied(): written by
# fused_multi_step solely so the deprecated accessor keeps answering
# during its sunset. New code uses plan_vmem_loop(...) — pure, and valid
# even when the compiled program came from a cache (this global is not).
_LAST_CHOICE: KernelChoice | None = None


def last_pad_applied() -> bool | None:
    """DEPRECATED: did the most recent fused_multi_step *trace* apply
    the pow2 pad? Stale whenever a cached compiled program is reused —
    compute the decision instead: plan_vmem_loop(...).pad_applied (pure,
    per-config, cache-proof)."""
    import warnings

    warnings.warn(
        "last_pad_applied() is deprecated: the module-global flag is only "
        "valid right after the call that traced the program; use "
        "plan_vmem_loop(shape, dtype, n_steps, ...).pad_applied instead",
        DeprecationWarning,
        stacklevel=2,
    )
    return None if _LAST_CHOICE is None else _LAST_CHOICE.pad_applied


def _next_pow2(n: int) -> int:
    return 1 << (int(n) - 1).bit_length()


def adoptable_vmem_chunk(v) -> bool:
    """May a tuning-cache chunk steer a VMEM multi-step kernel? Only a
    power of two >= 4: the kernels switch to a different fp body below
    chunk 4, and a pow2 preference guarantees gcd(n, v) lands in the
    SAME body-form class as the default gcd(n, DEFAULT_STEP_CHUNK) for
    every n — the algebra that keeps config="auto" bitwise-equal to the
    defaults no matter what step counts the caller brings. (The search
    space only emits 16/64/256; this guards hand-edited entries.)"""
    return (
        isinstance(v, int) and not isinstance(v, bool)
        and v >= 4 and (v & (v - 1)) == 0
    )


def plan_vmem_loop(shape, dtype, n_steps, chunk=None, body_form=None,
                   pad_pow2=None, config=None,
                   warn_on_cap=False) -> KernelChoice:
    """The VMEM-resident loop's trace-time decisions as a pure function
    of its inputs — the planning half of fused_multi_step, split out so
    callers (bench.py's ladder labels, the autotuner's program keys) can
    know what a given config WILL do without running it, and so
    `config="auto"` resolution has one seam.

    `config`: None/"default" keeps the passed/None-default knobs;
    "auto" consults the tuning cache (tuning/resolve.py, op
    "diffusion.vmem_loop") for any knob the caller left None, falling
    back to the module-constant hardware defaults on a miss. Resolved
    values end up in the returned record — explicit data, never mutated
    module state (GL02)."""
    shape = tuple(int(d) for d in shape)
    if config == "auto":
        from rocm_mpi_tpu.tuning import resolve as tuning_resolve

        tuned = tuning_resolve.resolve("diffusion.vmem_loop", shape, dtype)
        if tuned:
            if chunk is None and adoptable_vmem_chunk(tuned.get("chunk")):
                # A tuned chunk is a PREFERENCE the divisibility
                # contract still governs: gcd against a static n_steps
                # (mirroring the default policy); with a traced n the
                # caller's guarantee covers only the default chunk, so
                # auto stays hands-off there.
                if isinstance(n_steps, int):
                    chunk = math.gcd(n_steps, tuned["chunk"]) or None
            if body_form is None:
                body_form = tuned.get("body_form")
            if pad_pow2 is None:
                pad_pow2 = tuned.get("pad_pow2")
    elif config not in (None, "default"):
        raise ValueError(
            f"config must be None, 'default' or 'auto', got {config!r}"
        )
    if body_form is None:
        body_form = EQC_BODY_FORM
    if body_form not in ("eqc", "conly"):
        raise ValueError(
            f"body_form must be 'eqc' or 'conly', got {body_form!r}"
        )
    if pad_pow2 is None:
        pad_pow2 = VMEM_PAD_POW2
    nbytes = math.prod(shape) * _compute_itemsize(dtype)
    pad_applied: bool | None = None
    padded_shape = None
    if pad_pow2:
        padded = tuple(_next_pow2(d) for d in shape)
        pad_bytes = math.prod(padded) * _compute_itemsize(dtype)
        if padded == shape:
            pad_applied = None  # already pow2: nothing requested to do
        elif pad_bytes <= _VMEM_BLOCK_BUDGET_BYTES:
            pad_applied = True
            padded_shape = padded
            nbytes = pad_bytes  # the unroll cap must see the padded size
        else:
            pad_applied = False
    eff_chunk = resolve_step_chunk(n_steps, chunk, nbytes, warn_on_cap)
    return KernelChoice(
        op="diffusion.vmem_loop", dispatch="vmem-loop", chunk=eff_chunk,
        body_form=body_form, pad_requested=bool(pad_pow2),
        pad_applied=pad_applied, padded_shape=padded_shape,
    )


def _multi_step_kernel(T_ref, Cm_ref, out_ref, *, inv_d2, chunk,
                       body_form=None):
    """`chunk` steps of T += Cm · ∇²T, fully VMEM-resident.

    Tuned for the latency-bound small-field regime (the 252²/chip benchmark
    geometry): neighbors come from `jnp.roll` (single vreg lane/sublane
    rotate — measured ~2.5× faster on-chip than the pad+shifted-slice
    formulation, whose unaligned lane slices Mosaic lowers to multi-op
    shuffles), the Dirichlet boundary is enforced by `Cm` being zero outside
    the interior (so roll's wraparound neighbors are multiplied by exactly
    0.0 and edge cells stay fixed — bitwise identical to the masked-update
    formulation), and the step loop is fully unrolled (a non-unrolled
    in-kernel fori_loop costs ~2.5× in scalar-core loop overhead).

    For chunk ≥ 4 on small fields the update is algebraically refactored to
    T' = A∘T + Σ_ax c_ax∘(roll(T,-1,ax)+roll(T,+1,ax)) with A = 1−2Σc_ax
    and c_ax = Cm·inv_d2[ax] hoisted into a once-per-launch prologue —
    one fewer VPU op per axis per step, measured 8 % faster at 252² f32
    (425→390 ns/step, round 3, within-run A/B). When the
    spacing is equal on every axis (true of the benchmark geometry) the
    per-axis coefficients collapse to ONE array c = Cm·inv with
    A = 1−2·ndim·c and the roll pairs sum before the single multiply —
    one fewer VPU multiply per step again (within-run A/B:
    scripts/bench_kernel_forms.py). The Dirichlet hold stays exact in both
    forms: Cm==0 ⇒ c==0, A==1.0 ⇒ T'==T bitwise. Short chunks keep the
    direct form (the prologue would not amortize), and so do fields beyond
    _AC_FORM_MAX_BYTES: the prologue keeps up to ndim+1 extra field-sized
    arrays live across the unrolled loop, which near the 2 MB admission
    budget would blow the VMEM footprint the old form was validated under.
    """
    ndim = len(T_ref.shape)
    # bf16 is storage-only: budget the prologue at the f32 compute width.
    nbytes = _compute_itemsize(T_ref.dtype)
    for d in T_ref.shape:
        nbytes *= d
    T_in, Cm = _upcast_for_compute(T_ref[:], Cm_ref[:])

    if chunk >= 4 and nbytes <= _AC_FORM_MAX_BYTES:
        if all(inv == inv_d2[0] for inv in inv_d2):
            # Equal-spacing specialization: the per-axis coefficients
            # collapse to ONE array, c = Cm·inv, A = 1 − 2·ndim·c, and the
            # roll pairs sum BEFORE the multiply —
            # T' = A∘T + c∘Σ_ax(roll pair): one fewer VPU multiply per
            # step than the general A/c form. Same Dirichlet argument:
            # Cm==0 ⇒ c==0, A==1 ⇒ T'==T bitwise.
            c = Cm * inv_d2[0]
            # Two algebraically-identical final expressions over ONE
            # shared neighbor sum; the branch resolves at trace time.
            # "conly" (T' = T + c∘(s − 2·ndim·T)) reads one fewer VMEM
            # operand stream per step than "eqc" (no A array; 2·ndim is a
            # scalar) at the same VPU op count; the Dirichlet hold is
            # exact either way (c==0 ⇒ T'==T bitwise). Whether the saved
            # stream matters is the pending chip A/B's question
            # (scripts/bench_kernel_forms.py); CPU equivalence of both
            # forms is pinned in tests/test_pallas_kernels.py.
            if body_form is None:
                body_form = EQC_BODY_FORM
            if body_form not in ("eqc", "conly"):
                raise ValueError(
                    f"body_form must be 'eqc' or 'conly', got "
                    f"{body_form!r}"
                )
            conly = body_form == "conly"
            coef = (
                jnp.asarray(2.0 * ndim, c.dtype)
                if conly
                else 1.0 - (2.0 * ndim) * c
            )

            def body(_, T):
                s = None
                for ax in range(ndim):
                    r = jnp.roll(T, -1, ax) + jnp.roll(T, 1, ax)
                    s = r if s is None else s + r
                if conly:
                    return T + c * (s - coef * T)
                return coef * T + c * s

        else:
            cs = [Cm * inv for inv in inv_d2]
            A = 1.0 - 2.0 * functools.reduce(lambda a, b: a + b, cs)

            def body(_, T):
                acc = A * T
                for ax in range(ndim):
                    acc = acc + cs[ax] * (
                        jnp.roll(T, -1, ax) + jnp.roll(T, 1, ax)
                    )
                return acc

    else:

        def body(_, T):
            lap = None
            for ax in range(ndim):
                term = (
                    jnp.roll(T, -1, ax) + jnp.roll(T, 1, ax) - 2.0 * T
                ) * inv_d2[ax]
                lap = term if lap is None else lap + term
            return T + Cm * lap

    out_ref[:] = lax.fori_loop(0, chunk, body, T_in, unroll=True).astype(
        out_ref.dtype
    )


DEFAULT_STEP_CHUNK = 256
# The A/c refactoring of _multi_step_kernel (see its docstring) holds
# ndim+1 extra field-sized coefficient arrays VMEM-resident; allow it only
# well below the whole-block admission budget (validated at the 252²-class).
_AC_FORM_MAX_BYTES = 512 * 1024


def resolve_step_chunk(n_steps, chunk, nbytes, warn_on_cap=True):
    """The one chunk policy of the VMEM-resident multi-step kernels
    (fused_multi_step and ops.wave_kernels.wave_multi_step): default
    gcd(n_steps, DEFAULT_STEP_CHUNK) for static step counts; an explicit
    chunk must divide a static n_steps; and fields beyond the 256 KB
    unroll-friendly class cap the chunk at gcd(chunk, 16) — Mosaic compile
    time grows superlinearly in unrolled-steps × field size (252² compiles
    chunk=256 in tens of seconds; 512² at chunk=64 exceeded 9 minutes,
    measured) — warning when that degrades an explicitly requested chunk.
    """
    n_static = isinstance(n_steps, int)
    explicit = chunk is not None
    if chunk is None:
        chunk = (
            math.gcd(n_steps, DEFAULT_STEP_CHUNK)
            if n_static
            else DEFAULT_STEP_CHUNK
        )
    if n_static and n_steps % chunk != 0:
        raise ValueError(f"chunk {chunk} must divide n_steps {n_steps}")
    if nbytes > 256 * 1024:
        capped = math.gcd(chunk, 16) or 1
        if explicit and warn_on_cap and capped != chunk:
            import warnings

            warnings.warn(
                f"chunk degraded: {chunk} requested but the {nbytes}-byte "
                f"field exceeds the 256 KB unroll-friendly class; running "
                f"chunk={capped} (longer unrolls stall the Mosaic compiler).",
                stacklevel=3,
            )
        chunk = capped
    return chunk


def fused_multi_step(T, Cp, lam, dt, spacing, n_steps, chunk=None, interpret=None,
                     warn_on_cap=True, body_form=None, pad_pow2=None,
                     config=None):
    """Advance a *single-shard* field `n_steps` barely leaving VMEM.

    `body_form` ('eqc'/'conly') and `pad_pow2` are explicit TRACE-TIME
    switches for the kernel-form A/B (bench.py's stage-2.5 ladder passes
    them per rung); None defaults to the module constants EQC_BODY_FORM /
    VMEM_PAD_POW2 — the measured hardware defaults. Explicit kwargs, not
    global mutation: a cached/reused jitted advance would silently ignore
    a mutated module global, but a changed kwarg changes the trace
    (ADVICE r5 #1). `config="auto"` fills any knob left None from the
    persistent tuning cache instead (plan_vmem_loop → tuning/resolve.py;
    a cache miss keeps the hand-picked defaults, bitwise-identically).

    TPU-only optimization (no reference analog — the GPU version must round-
    trip HBM every step): the kernel runs `chunk` steps per invocation with
    the field VMEM-resident, and an outer XLA loop repeats it — one HBM
    round-trip every `chunk` steps instead of 3 whole-array passes per step.
    `chunk` is static (Mosaic compile time scales with it; a dynamic
    in-kernel trip count stalls the compiler) and must divide `n_steps`;
    default gcd(n_steps, 256), and on fields larger than the 252²-class
    (256 KB) the effective chunk is capped at gcd(chunk, 16) — larger
    unrolls over that many vregs stall the Mosaic compiler for minutes.
    The outer trip count is dynamic, so one compiled program serves every
    `n_steps` with the same chunk. Global
    boundary = block boundary (Dirichlet).

    bf16 fields are storage-only (r4): the kernel computes the whole
    chunk in f32 and rounds back once per chunk, so bf16 keeps its
    traffic savings without per-step quantization drift
    (_upcast_for_compute; error curve in
    docs/bf16_error_cpu252_*_r4.txt). Admission and chunk policy
    therefore budget at f32 width.
    """
    if interpret is None:
        interpret = _interpret_default()
    if not _supports_compiled(T.dtype) and not interpret:
        raise TypeError(f"Mosaic does not support {T.dtype}")
    nbytes = _compute_nbytes(T)
    if nbytes > _VMEM_BLOCK_BUDGET_BYTES:
        raise ValueError(
            f"field of {nbytes} bytes (f32 compute width) exceeds the "
            f"VMEM-resident budget ({_VMEM_BLOCK_BUDGET_BYTES}); use the "
            "per-step path"
        )
    lam, dt = float(lam), float(dt)
    inv_d2 = tuple(1.0 / (float(d) * float(d)) for d in spacing)
    # Masked update coefficient, computed ONCE per advance call (not per
    # step) — for the single-shard use the block edge IS the global
    # boundary (the reference's interior-only guard, perf.jl:7).
    Cm = _edge_masked_cm(T, Cp, lam, dt)
    orig_shape = T.shape
    choice = plan_vmem_loop(
        T.shape, T.dtype, n_steps, chunk=chunk, body_form=body_form,
        pad_pow2=pad_pow2, config=config, warn_on_cap=warn_on_cap,
    )
    global _LAST_CHOICE
    _LAST_CHOICE = choice  # deprecation shim only (last_pad_applied)
    if choice.pad_applied:
        widths = tuple(
            (0, p - d) for p, d in zip(choice.padded_shape, T.shape)
        )
        T = jnp.pad(T, widths)  # pad values are frozen (Cm pads to 0)
        Cm = jnp.pad(Cm, widths)
    elif choice.pad_applied is False:
        # Requested but skipped: without a loud record, a bench row at
        # a larger geometry would carry a 'pad256' label for a program
        # that actually ran unpadded (ADVICE r5 #4).
        import warnings

        warnings.warn(
            f"pad_pow2 requested but SKIPPED: the padded field would "
            f"exceed the VMEM budget ({_VMEM_BLOCK_BUDGET_BYTES}); the "
            "program runs unpadded — do not label this measurement 'pad'",
            stacklevel=2,
        )
    kernel = functools.partial(_multi_step_kernel, inv_d2=inv_d2,
                               chunk=choice.chunk,
                               body_form=choice.body_form)
    run_chunk = pl.pallas_call(
        kernel,
        out_shape=_out_struct(T.shape, T),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        interpret=interpret,
    )
    # For traced n_steps divisibility can't be checked at trace time: the
    # trip count floors, so a non-multiple silently rounds DOWN to the
    # nearest chunk — callers with dynamic n must guarantee divisibility
    # (run_vmem_resident does, via gcd).
    out = lax.fori_loop(
        0, n_steps // choice.chunk, lambda _, x: run_chunk(x, Cm), T
    )
    if out.shape != orig_shape:
        out = out[tuple(slice(0, d) for d in orig_shape)]
    return out


def multi_step_cm(T, Cm, spacing, n_steps: int, interpret=None):
    """`n_steps` unrolled roll-based steps on a VMEM-resident block with a
    caller-supplied masked update coefficient `Cm` (same contract as the
    coefficient `fused_multi_step` builds internally: dt·λ/Cp where the
    cell updates, exactly 0.0 where it is held fixed).

    This is the local compute of the deep-halo sweep
    (parallel.deep_halo): the caller pads the block and zeroes `Cm` on
    ghost/Dirichlet cells; `n_steps` must not exceed the ghost width.
    """
    if interpret is None:
        interpret = _interpret_default()
    if not _supports_compiled(T.dtype) and not interpret:
        raise TypeError(f"Mosaic does not support {T.dtype}")
    if T.shape != Cm.shape:
        raise ValueError(f"shape mismatch: T {T.shape} vs Cm {Cm.shape}")
    nbytes = _compute_nbytes(T)
    if nbytes > _VMEM_BLOCK_BUDGET_BYTES:
        raise ValueError(
            f"padded block of {nbytes} bytes (f32 compute width) exceeds "
            f"the VMEM-resident budget ({_VMEM_BLOCK_BUDGET_BYTES}); for "
            "HBM-resident blocks use multi_step_cm_hbm (the deep-halo "
            "sweep routes there automatically) or the per-step variants"
        )
    inv_d2 = tuple(1.0 / (float(d) * float(d)) for d in spacing)
    kernel = functools.partial(
        _multi_step_kernel, inv_d2=inv_d2, chunk=int(n_steps)
    )
    return pl.pallas_call(
        kernel,
        out_shape=_out_struct(T.shape, T),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        interpret=interpret,
    )(T, Cm)


# ---------------------------------------------------------------------------
# Temporal blocking for HBM-resident fields: k steps per memory sweep.
# ---------------------------------------------------------------------------


def edge_mask(shape):
    """Boolean mask: True on the global Dirichlet edge of an unsharded
    block (every axis's first/last cell). The one edge-detection used by
    both mask-as-data contracts (diffusion's edge_masked_cm, the wave
    workload's interior_mask)."""
    mask = None
    for ax in range(len(shape)):
        idx = lax.broadcasted_iota(jnp.int32, shape, ax)
        m = (idx == 0) | (idx == shape[ax] - 1)
        mask = m if mask is None else (mask | m)
    return mask


def edge_masked_cm(T, Cp, lam, dt):
    """(dt·λ)/Cp on the interior, exactly 0.0 on the global Dirichlet edge.

    The masked update coefficient of the Cm-contract kernels
    (fused_step_cm / masked_step / multi_step_cm): cells with Cm == 0.0
    stay bit-identically fixed (old + 0.0·lap == old), carrying the
    reference's interior-only guard (perf.jl:7) as data. Unsharded form —
    the block edge IS the global boundary; the sharded form masks via
    parallel.halo.global_boundary_mask instead.
    """
    return jnp.where(edge_mask(T.shape), jnp.zeros_like(Cp), (dt * lam) / Cp)


_edge_masked_cm = edge_masked_cm  # internal alias (pre-r3 name)


def _tb_kernel(Tu_ref, Tc_ref, Td_ref, Cu_ref, Cc_ref, Cd_ref, o_ref, *,
               inv_d2, k, g, tm):
    """Advance one axis-0 stripe by `k` steps from a (g+tm+g)-row slab.

    Stripe i's output rows [i·tm, (i+1)·tm) after k steps depend on input
    rows [i·tm−k, (i+1)·tm+k); with k ≤ g the slab of the core stripe plus
    one g-row ghost block per side covers that light cone. Ghost rows feed
    transient values whose own errors (from the slab edge's roll wraparound)
    propagate one row per step and never reach the core in k ≤ g steps.
    At the domain's first/last stripe the clamped ghost blocks are replaced
    by zeros — the same zero-ghost convention as the VMEM-resident kernel
    (those values only ever multiply into cells the zero `Cm` edge ring
    keeps Dirichlet-fixed).
    """
    i = pl.program_id(0)
    n_i = pl.num_programs(0)
    zg = jnp.zeros_like(Tu_ref[:])
    T = jnp.concatenate(
        [jnp.where(i == 0, zg, Tu_ref[:]), Tc_ref[:],
         jnp.where(i == n_i - 1, zg, Td_ref[:])], 0)
    Cm = jnp.concatenate(
        [jnp.where(i == 0, zg, Cu_ref[:]), Cc_ref[:],
         jnp.where(i == n_i - 1, zg, Cd_ref[:])], 0)
    T, Cm = _upcast_for_compute(T, Cm)  # bf16 storage, f32 sweep arithmetic
    ndim = T.ndim
    for _ in range(k):
        lap = None
        for ax in range(ndim):
            term = (
                jnp.roll(T, -1, ax) + jnp.roll(T, 1, ax) - 2.0 * T
            ) * inv_d2[ax]
            lap = term if lap is None else lap + term
        T = T + Cm * lap
    o_ref[:] = T[g:g + tm].astype(o_ref.dtype)


def _stripe_ghost_specs(tm, g, n0, rest):
    """(core, gup, gdn) BlockSpecs shared by the ghost-block stripe
    pipelines (_tb_kernel and _per_step_kernel): core stripe i (tm rows)
    plus the clamped g-row ghost blocks above/below it. The domain-edge
    clamps re-read an interior block; the kernels zero those via the
    i==0 / i==n-1 selects."""
    r = tm // g
    zeros = (0,) * len(rest)
    core = pl.BlockSpec(
        (tm,) + rest, lambda i: (i,) + zeros, memory_space=pltpu.VMEM
    )
    gup = pl.BlockSpec(
        (g,) + rest,
        lambda i: (jnp.maximum(i * r - 1, 0),) + zeros,
        memory_space=pltpu.VMEM,
    )
    gdn = pl.BlockSpec(
        (g,) + rest,
        lambda i: (jnp.minimum((i + 1) * r, n0 // g - 1),) + zeros,
        memory_space=pltpu.VMEM,
    )
    return core, gup, gdn


DEFAULT_TB_STEPS = 8  # HBM temporal blocking: bounded by the ghost rows
# Deep-halo sweep depth: single-chip optimum at 252² re-measured with the
# A/c kernel form (r3: k=8 1.02 µs, k=16 0.889, k=32 0.848 — the prologue
# amortizes further with depth); on a pod slice larger k also divides the
# message count. HBM-resident shards cap at DEFAULT_TB_STEPS regardless.
DEFAULT_DEEP_STEPS = 32
_TB_G = 8  # tb-sweep ghost-block rows (the TPU sublane tile) = max k/sweep
_TB_TM = 16  # stripe height; with _TB_G ghosts, tuned to the VMEM limit
assert _TB_TM % _TB_G == 0  # _stripe_ghost_specs' index maps require it
_TB_MAX_STEPS = 16  # deepest supported sweep (the (g=16, tm=32) geometry)


def tb_geometry(k: int) -> tuple[int, int]:
    """(ghost rows g, stripe height tm) for a k-step temporal-blocked
    sweep. k <= 8 keeps the chip-validated production geometry (8, 16);
    deeper sweeps (k <= 16) use (16, 32) — half the HBM passes per step
    (5 per 16 steps vs 5 per 8), at a (tm+2g)=64-row slab whose Mosaic
    compile envelope at very wide rows is measured by
    scripts/bench_tb_stripes.py's (32,16,16) case before any default
    changes. Both satisfy tm % g == 0 (_stripe_ghost_specs) and k <= g
    (the light-cone bound of _tb_kernel)."""
    if 1 <= k <= _TB_G:
        return _TB_G, _TB_TM
    if _TB_G < k <= _TB_MAX_STEPS:
        return 16, 32
    raise ValueError(
        f"temporal-blocked sweeps support 1 <= k <= {_TB_MAX_STEPS}, "
        f"got {k}"
    )


def tb_slab_fits(k: int, shape, dtype) -> bool:
    """True when a k-deep sweep's in-kernel slab — (tm+2g) rows at the f32
    compute width — fits the measured Mosaic compile envelope
    (_PS_SLAB_BUDGET_BYTES). The deep (16, 32) geometry's 64-row slab
    exceeds it for f32 rows wider than ~9.7k columns (the flagship 12288²
    included), so callers must gate on this instead of crashing the
    compile: fused_multi_step_hbm/multi_step_cm_hbm raise with a clear
    message, and the deep-halo routing falls back to the jnp path."""
    g, tm = tb_geometry(k)
    row = _compute_itemsize(dtype)
    for n in shape[1:]:
        row *= n
    return (tm + 2 * g) * row <= _PS_SLAB_BUDGET_BYTES


def hbm_class_edge(itemsize: int = 4, k: int = DEFAULT_TB_STEPS) -> int:
    """Smallest square-shard edge whose k-padded block exceeds the
    VMEM-resident budget — i.e. the smallest shard a k-deep sweep routes
    to the temporal-blocked HBM kernel (multi_step_cm_hbm) instead of the
    VMEM loop. The ONE sizing used by the routing-coverage checks
    (__graft_entry__ dryrun, tests/test_overlap.py), so a budget or
    geometry retune cannot leave them asserting a stale routing claim:
    the edge iterates in tb_geometry(k) stripe-height units, which (with
    2k divisible by that tm for the supported depths) keeps the k-padded
    row count stripe-divisible by construction.
    """
    g, tm = tb_geometry(k)
    if (2 * k) % tm != 0:
        raise ValueError(
            f"hbm_class_edge needs 2k divisible by the stripe height "
            f"(k={k}, tm={tm}) so the padded row count stays "
            "stripe-divisible; pass k=8 or k=16"
        )
    n = tm  # n % tm == 0 and 2k % tm == 0 ⇒ (n + 2k) % tm == 0
    while (n + 2 * k) ** 2 * itemsize <= _VMEM_BLOCK_BUDGET_BYTES:
        n += tm
    return n


def fused_multi_step_hbm(T, Cp, lam, dt, spacing, n_steps, block_steps=None,
                         interpret=None):
    """Advance a *single-shard* HBM-resident field `n_steps` via temporal
    blocking: each memory sweep advances the whole field `block_steps`
    steps. Per sweep, each stripe loads tm+2g rows per tm output rows —
    with the (g, tm) geometry picked per depth by tb_geometry: k <= 8 at
    (8, 16) is 2 reads of T, 2 of Cm, 1 write = 5 whole-array passes per
    k steps (~0.6 passes/step at k=8); k <= 16 at (16, 32) is the same 5
    passes per 16 steps (~0.3/step) — instead of the 3 passes *per step*
    the per-step path (and the reference's fused GPU kernel,
    perf.jl:3-13) pays by construction. The TPU grid executes
    stripes sequentially, so sweep s+1 only starts after sweep s wrote its
    stripes; correctness needs no inter-stripe synchronization beyond the
    light-cone ghost blocks (see _tb_kernel). bf16 fields are
    storage-only (r4): slabs upcast to f32 in-kernel and round back once
    per sweep — bf16 HBM traffic, f32 sweep arithmetic.

    Requires n_steps % block_steps == 0 (static check when n_steps is a
    Python int; for traced n_steps the trip count floors), axis-0 length
    divisible by the depth's stripe height (tb_geometry: 16 for k <= 8,
    32 beyond), and — for the deeper geometry — rows narrow enough for
    the slab to fit the Mosaic compile envelope (tb_slab_fits). Measured on
    one v5e chip at 12288² f32, k=8: 1.61 ms/step (chip_smoke.py, PR 21;
    PERF.md) — more steps per second than a 3-passes-per-step design can
    reach at the chip's HBM bandwidth.
    """
    if interpret is None:
        interpret = _interpret_default()
    if not _supports_compiled(T.dtype) and not interpret:
        raise TypeError(f"Mosaic does not support {T.dtype}")
    k = DEFAULT_TB_STEPS if block_steps is None else block_steps
    if not 1 <= k <= _TB_MAX_STEPS:
        raise ValueError(
            f"block_steps must be in [1, {_TB_MAX_STEPS}], got {k}"
        )
    g, tm = tb_geometry(k)  # ghost rows (>= k) and stripe height
    if not tb_slab_fits(k, T.shape, T.dtype):
        raise ValueError(
            f"a k={k} sweep's (tm+2g)={tm + 2 * g}-row slab exceeds the "
            f"Mosaic compile envelope ({_PS_SLAB_BUDGET_BYTES} B at f32 "
            "compute width) for rows this wide; use k <= "
            f"{_TB_G} or a narrower field"
        )
    n0 = T.shape[0]
    # n0 % tm == 0 with tm a multiple of g also gives the ghost-block
    # alignment the stripe specs need.
    if n0 % tm != 0 or (n0 // tm) < 2:
        raise ValueError(
            f"axis-0 length {n0} must be a multiple of {tm} (>= 2 stripes)"
        )
    if isinstance(n_steps, int) and n_steps % k != 0:
        raise ValueError(f"n_steps {n_steps} must be a multiple of {k}")
    lam, dt = float(lam), float(dt)
    inv_d2 = tuple(1.0 / (float(d) * float(d)) for d in spacing)
    Cm = _edge_masked_cm(T, Cp, lam, dt)
    sweep = _make_tb_sweep(T, inv_d2, k, g, tm, interpret)
    return lax.fori_loop(0, n_steps // k, lambda _, x: sweep(x, Cm), T)


def _make_tb_sweep(T, inv_d2, k, g, tm, interpret):
    """Build sweep(T, Cm) -> T advanced k steps, one temporal-blocked
    memory pass (the pallas_call shared by fused_multi_step_hbm and
    multi_step_cm_hbm). Caller guarantees the shape constraints."""
    core, gup, gdn = _stripe_ghost_specs(tm, g, T.shape[0], T.shape[1:])
    kernel = functools.partial(_tb_kernel, inv_d2=inv_d2, k=k, g=g, tm=tm)
    call = pl.pallas_call(
        kernel,
        out_shape=_out_struct(T.shape, T),
        grid=(T.shape[0] // tm,),
        in_specs=[gup, core, gdn, gup, core, gdn],
        out_specs=core,
        interpret=interpret,
    )
    return lambda T, Cm: call(T, T, T, Cm, Cm, Cm)


def multi_step_cm_hbm(T, Cm, spacing, n_steps: int, interpret=None):
    """One temporal-blocked sweep of `n_steps` steps on an *HBM-resident*
    block with a caller-supplied masked coefficient — the large-shard form
    of multi_step_cm (same contract: Cm is dt·λ/Cp where the cell updates,
    exactly 0.0 where held; the caller crops sweep-edge staleness).

    This is the local compute of deep-halo sweeps on shards too big for
    VMEM (parallel.deep_halo): the k-wide exchanged ghost ring bounds the
    block-edge staleness exactly as the VMEM kernel's roll wraparound
    does, and the in-sweep stripe ghosts (g rows) bound the stripe-level
    staleness, so `n_steps` ≤ g and ≤ ghost width keeps the crop exact.
    Requires axis-0 length divisible by the depth's stripe height
    (tb_geometry) and, for the deeper geometry, rows that fit the Mosaic
    compile envelope (tb_slab_fits — the deep-halo router pre-checks and
    falls back to the jnp path instead of tripping this).
    """
    if interpret is None:
        interpret = _interpret_default()
    if not _supports_compiled(T.dtype) and not interpret:
        raise TypeError(f"Mosaic does not support {T.dtype}")
    if T.shape != Cm.shape:
        raise ValueError(f"shape mismatch: T {T.shape} vs Cm {Cm.shape}")
    if not 1 <= n_steps <= _TB_MAX_STEPS:
        raise ValueError(
            f"n_steps must be in [1, {_TB_MAX_STEPS}] per HBM sweep, got "
            f"{n_steps} (the g-row stripe ghosts bound the in-sweep "
            "light cone)"
        )
    g, tm = tb_geometry(int(n_steps))
    if not tb_slab_fits(int(n_steps), T.shape, T.dtype):
        raise ValueError(
            f"a k={n_steps} sweep's (tm+2g)={tm + 2 * g}-row slab exceeds "
            f"the Mosaic compile envelope for rows this wide; use k <= "
            f"{_TB_G} or a narrower block (the deep-halo router falls "
            "back to the jnp path automatically)"
        )
    n0 = T.shape[0]
    if n0 % tm != 0 or (n0 // tm) < 2:
        raise ValueError(
            f"axis-0 length {n0} must be a multiple of {tm} (>= 2 stripes)"
        )
    inv_d2 = tuple(1.0 / (float(d) * float(d)) for d in spacing)
    return _make_tb_sweep(T, inv_d2, int(n_steps), g, tm, interpret)(T, Cm)


# ---------------------------------------------------------------------------
# Unsharded per-step sweep: one kernel per step for HBM-resident fields —
# the reference-parity rung (one whole-field pass per step, perf.jl:47-52)
# without the pad/divide/where op chain around it.
# ---------------------------------------------------------------------------


def _per_step_kernel(Tu_ref, Tc_ref, Td_ref, Cm_ref, o_ref, *, inv_d2, g, tm):
    """Advance one axis-0 stripe by ONE step from a (g+tm+g)-row slab.

    The k=1 specialization of the temporal-blocking structure (_tb_kernel):
    because only the immediately adjacent row feeds a 1-step update, the
    coefficient needs no ghost blocks — Cm is read core-only, cutting a
    whole array pass per step versus the k-step slab. Domain-edge ghost
    blocks are zeroed; their values only multiply into cells the zero-Cm
    edge ring holds fixed. Requires the row count divisible by the stripe
    height: a partial trailing stripe would feed Pallas-masked (undefined)
    rows into the last valid row's neighborhood, where NaN·0.0 could leak
    through the Cm guard — masked_step falls back to the padded-contract
    kernel for such shapes.
    """
    i = pl.program_id(0)
    n_i = pl.num_programs(0)
    zg = jnp.zeros_like(Tu_ref[:])
    T = jnp.concatenate(
        [jnp.where(i == 0, zg, Tu_ref[:]), Tc_ref[:],
         jnp.where(i == n_i - 1, zg, Td_ref[:])], 0)
    T, Tc, Cm = _upcast_for_compute(T, Tc_ref[:], Cm_ref[:])
    lap = None
    for ax in range(T.ndim):
        term = (
            jnp.roll(T, -1, ax) + jnp.roll(T, 1, ax) - 2.0 * T
        ) * inv_d2[ax]
        lap = term if lap is None else lap + term
    o_ref[:] = (Tc + Cm * lap[g:g + tm]).astype(o_ref.dtype)


def _masked_step_striped(T, Cm, inv_d2, interpret, tm, g):
    n0, rest = T.shape[0], T.shape[1:]
    core, gup, gdn = _stripe_ghost_specs(tm, g, n0, rest)
    kernel = functools.partial(_per_step_kernel, inv_d2=inv_d2, g=g, tm=tm)
    return pl.pallas_call(
        kernel,
        out_shape=_out_struct(T.shape, T),
        grid=(n0 // tm,),
        in_specs=[gup, core, gdn, core],
        out_specs=core,
        interpret=interpret,
    )(T, T, T, Cm)


def masked_step(T, Cm, spacing, interpret=None, tm=None, config=None):
    """Unsharded per-step update with the mask folded into `Cm`: one Pallas
    program per step.

    The reference-parity per-step schedule (one whole-field sweep per step,
    perf.jl:47-52) for a single-device grid: `Cm` (edge_masked_cm) carries
    both (dt·λ)/Cp and the Dirichlet guard, computed once per run — so each
    step is exactly one kernel, with no ghost-pad copy, no per-step divide,
    and no where-mask pass. Dispatch: VMEM-resident roll kernel
    (multi_step_cm, n=1) for fields that fit; the ghost-block striped sweep
    for HBM-resident fields with stripe-divisible rows; zero-ghost pad +
    the padded-contract striped kernel for everything else.

    `tm` overrides the stripe height (tuning knob — the threads=(32,8)
    analog); must be a multiple of 8. `config="auto"` consults the tuning
    cache (op "diffusion.masked_step") for a tm the caller left unset; a
    cached tm that no longer satisfies this shape's stripe constraints is
    ignored silently (the automatic height picks instead) — an auto
    resolve must never be louder than the default path.
    """
    if T.shape != Cm.shape:
        raise ValueError(f"shape mismatch: T {T.shape} vs Cm {Cm.shape}")
    if interpret is None:
        interpret = _interpret_default()
    if not _supports_compiled(T.dtype) and not interpret:
        raise TypeError(f"Mosaic does not support {T.dtype}")
    nbytes = _compute_nbytes(T)
    if nbytes <= _VMEM_BLOCK_BUDGET_BYTES:
        return multi_step_cm(T, Cm, spacing, 1, interpret=interpret)
    inv_d2 = tuple(1.0 / (float(d) * float(d)) for d in spacing)
    g = 8
    n0 = T.shape[0]
    tm_explicit = tm is not None
    if config == "auto" and tm is None:
        from rocm_mpi_tpu.tuning import resolve as tuning_resolve

        tuned = tuning_resolve.resolve(
            "diffusion.masked_step", T.shape, T.dtype
        )
        if tuned and tuned.get("tm"):
            cand = int(tuned["tm"])
            slab_unit = (
                math.prod(T.shape[1:]) * _compute_itemsize(T.dtype)
            )
            if (
                cand % g == 0
                and n0 % cand == 0
                and (cand + 2 * g) * slab_unit <= _PS_SLAB_BUDGET_BYTES
            ):
                tm = cand
    elif config not in (None, "default", "auto"):
        raise ValueError(
            f"config must be None, 'default' or 'auto', got {config!r}"
        )
    if tm is None:
        row_bytes = T.dtype.itemsize
        for n in T.shape[1:]:
            row_bytes *= n
        base = _stripe_height(row_bytes)
        # Taller stripes amortize the per-stripe DMA overhead (measured on
        # v5e at 12288² f32: tm=32 ≈ 254 GB/s T_eff vs tm=16 ≈ 241): take
        # the tallest multiple of g up to 2× the budget height that divides
        # the row count AND whose in-kernel slab (tm+2g rows, concatenated
        # + ~3 lap temporaries, computed at ≥f32 width even for bf16
        # inputs) stays under the measured Mosaic compile boundary
        # (~2.4 MB f32-equivalent slab: f32 12288²/tm=48, 8192²/tm=64 and
        # bf16 12288²/tm=64 all fail to compile beyond it).
        # No candidate fitting → None → the pad fallback (very wide rows,
        # where even the base slab would blow the compile boundary).
        slab_unit = (row_bytes // T.dtype.itemsize) * max(
            T.dtype.itemsize, 4
        )
        tm = next(
            (
                c
                for c in range(2 * base, g - 1, -g)
                if n0 % c == 0
                and (c + 2 * g) * slab_unit <= _PS_SLAB_BUDGET_BYTES
            ),
            None,
        )
    strip_ok = (
        tm is not None
        and T.ndim in (2, 3)
        and tm % g == 0
        and n0 % tm == 0
        and n0 % g == 0
    )
    if strip_ok:
        return _masked_step_striped(T, Cm, inv_d2, interpret, tm, g)
    if tm_explicit:
        import warnings

        warnings.warn(
            f"masked_step tm={tm} ignored: the striped path needs a 2D/3D "
            f"field with tm and the row count ({n0}) divisible by {g} and "
            "n0 % tm == 0; running the pad + padded-contract fallback "
            "instead.",
            stacklevel=2,
        )
    # General-shape fallback: zero ghost ring + the padded-contract striped
    # kernel (edge Cm = 0.0 makes the ghost values irrelevant).
    Tp = jnp.pad(T, [(1, 1)] * T.ndim)
    return fused_step_cm(Tp, Cm, spacing, interpret=interpret)

"""Pallas TPU kernels for the acoustic-wave workload (framework-generality
demo — no reference analog; the reference ships exactly one physics model).

The leapfrog update U⁺ = 2U − U⁻ + dt²·c²·∇²U is a 3-operand stencil: the
same padded-block contract as the diffusion kernels
(ops.pallas_kernels.fused_step_padded), with a second state array read
core-only. Note the Dirichlet guard CANNOT ride a zeroed coefficient alone
(c²==0 gives U⁺ = 2U − U⁻ ≠ U): the per-step path masks explicitly in the
caller (the diffusion 'shard' variant structure), and the VMEM-resident
multi-step kernel rewrites the update as

    U⁺ = U + M∘(U − U⁻) + Cw∘∇²U,   M = interior mask, Cw = dt²·c²·M

which holds edge cells bitwise (M==0 and Cw==0 ⇒ U⁺==U) — the wave
edition of the diffusion kernels' mask-as-data contract.
"""

from __future__ import annotations

import functools

import jax.numpy as jnp
from jax import lax
from rocm_mpi_tpu.utils.compat import pallas as pl
from rocm_mpi_tpu.utils.compat import pallas_tpu as pltpu

from rocm_mpi_tpu.ops.pallas_kernels import (
    _VMEM_BLOCK_BUDGET_BYTES,
    _compute_nbytes,
    _interpret_default,
    _lap_from_padded,
    _out_struct,
    _supports_compiled,
    _upcast_for_compute,
)


def wave_step_padded(Up, Uprev, C2, dt, spacing):
    """Candidate leapfrog update for every core cell of the padded block
    (pure jnp). `Up` is width-1-padded displacement; `Uprev`/`C2` are
    core-shaped. Same contract as ops.diffusion.step_fused_padded: the
    caller supplies ghosts and masks global-boundary cells. The one
    stencil definition — the Pallas kernel below computes the same
    expression in VMEM, and the VMEM-overflow fallback calls this.
    """
    inv_d2 = tuple(1.0 / (d * d) for d in spacing)
    core = tuple(slice(1, -1) for _ in range(C2.ndim))
    return 2.0 * Up[core] - Uprev + (dt * dt) * C2 * _lap_from_padded(
        Up, inv_d2
    )


def wave_step_padded_geom(Up, Uprev, C2, dt2, inv_d2):
    """`wave_step_padded` with the geometry PRECOMPUTED as operands:
    `dt2` = dt² and `inv_d2` = per-axis 1/spacing², both computed on the
    HOST in f64 then cast — the same rounding the python-float path
    above hits at its weak-typed casts. The ladder lane kernel: a
    laddered batch carries per-lane dt²/inv-spacing² so one compiled
    program serves lanes of different original shapes bitwise-equal to
    their standalone runs (ops.diffusion.step_fused_padded_geom has the
    ulp rationale)."""
    core = tuple(slice(1, -1) for _ in range(C2.ndim))
    return 2.0 * Up[core] - Uprev + dt2 * C2 * _lap_from_padded(
        Up, inv_d2
    )


def _wave_kernel_whole(Up_ref, Uprev_ref, C2_ref, out_ref, *, dt2, inv_d2):
    Up, Uprev, C2 = _upcast_for_compute(Up_ref[:], Uprev_ref[:], C2_ref[:])
    core = tuple(slice(1, -1) for _ in range(Up.ndim))
    out_ref[:] = (
        2.0 * Up[core] - Uprev + dt2 * C2 * _lap_from_padded(Up, inv_d2)
    ).astype(out_ref.dtype)


def wave_step_padded_pallas(Up, Uprev, C2, dt, spacing, interpret=None):
    """Candidate leapfrog update for every core cell of a padded block.

    `Up` is the width-1-padded displacement (ghosts from exchange_halo);
    `Uprev` and `C2` (squared wave speed) are core-shaped. Whole-block VMEM
    kernel; falls back to the IDENTICAL-semantics jnp padded form in two
    cases (ADVICE r3): blocks beyond the VMEM budget, and dtypes Mosaic
    cannot compile (f64 on a real TPU — unlike the diffusion kernels,
    which raise there; the wave workload is the layering demo, not the
    tuned flagship, so a chip benchmark of wave f64 times the jnp path).
    """
    if interpret is None:
        interpret = _interpret_default()
    nbytes = _compute_nbytes(C2)
    if (not _supports_compiled(Up.dtype) and not interpret) or (
        nbytes > _VMEM_BLOCK_BUDGET_BYTES
    ):
        return wave_step_padded(Up, Uprev, C2, dt, spacing)
    dt2 = float(dt) * float(dt)
    inv_d2 = tuple(1.0 / (float(d) * float(d)) for d in spacing)
    kernel = functools.partial(_wave_kernel_whole, dt2=dt2, inv_d2=inv_d2)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kernel,
        out_shape=_out_struct(C2.shape, C2),
        in_specs=[vmem, vmem, vmem],
        out_specs=vmem,
        interpret=interpret,
        name="wave_step_padded_pallas",
    )(Up, Uprev, C2)


def wave_step_padded_masked(Up, Uprev, M, Cw, spacing):
    """Masked-contract candidate leapfrog update (pure jnp): `Up` is the
    width-1-padded displacement; `Uprev`, the interior mask `M` (1.0 on
    updating cells, exactly 0.0 on global Dirichlet cells) and the masked
    coefficient `Cw = dt²·c²·M` are core-shaped data operands prepared
    once per program (models.wave `_mask_prepare`).

    The hold is a branch-free select, M·cand + (1−M)·U: on updating cells
    (M==1) `cand = 2U − U⁻ + Cw·∇²U` is the SAME left-associated fp
    expression as `wave_step_padded`, so results are bitwise identical
    there; on held cells the result is U bitwise. No caller-side
    whole-shard `where` — the wave edition of the diffusion Cm contract
    (the leapfrog needs M itself because c²==0 alone gives 2U − U⁻ ≠ U,
    see the module docstring).
    """
    inv_d2 = tuple(1.0 / (d * d) for d in spacing)
    core = tuple(slice(1, -1) for _ in range(M.ndim))
    Uc = Up[core]
    cand = 2.0 * Uc - Uprev + Cw * _lap_from_padded(Up, inv_d2)
    return M * cand + (1.0 - M) * Uc


def _wave_kernel_whole_masked(Up_ref, Uprev_ref, M_ref, Cw_ref, out_ref, *,
                              inv_d2):
    Up, Uprev, M, Cw = _upcast_for_compute(
        Up_ref[:], Uprev_ref[:], M_ref[:], Cw_ref[:]
    )
    core = tuple(slice(1, -1) for _ in range(M.ndim))
    Uc = Up[core]
    cand = 2.0 * Uc - Uprev + Cw * _lap_from_padded(Up, inv_d2)
    out_ref[:] = (M * cand + (1.0 - M) * Uc).astype(out_ref.dtype)


def wave_step_padded_masked_pallas(Up, Uprev, M, Cw, spacing,
                                   interpret=None):
    """Pallas whole-block form of the masked-contract leapfrog update
    (the hide rung's region kernel). Falls back to the identical-semantics
    jnp form for blocks beyond the VMEM budget and for dtypes Mosaic
    cannot compile (f64 on a real chip) — the same policy as
    wave_step_padded_pallas."""
    if interpret is None:
        interpret = _interpret_default()
    nbytes = _compute_nbytes(M)
    if (not _supports_compiled(Up.dtype) and not interpret) or (
        nbytes > _VMEM_BLOCK_BUDGET_BYTES
    ):
        return wave_step_padded_masked(Up, Uprev, M, Cw, spacing)
    inv_d2 = tuple(1.0 / (float(d) * float(d)) for d in spacing)
    kernel = functools.partial(_wave_kernel_whole_masked, inv_d2=inv_d2)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kernel,
        out_shape=_out_struct(M.shape, M),
        in_specs=[vmem, vmem, vmem, vmem],
        out_specs=vmem,
        interpret=interpret,
        name="wave_step_padded_masked_pallas",
    )(Up, Uprev, M, Cw)


# ---------------------------------------------------------------------------
# Whole-loop-in-VMEM leapfrog: the wave edition of the diffusion flagship's
# fused_multi_step schedule (one HBM round-trip per `chunk` steps).
# ---------------------------------------------------------------------------


def masked_leapfrog_step(U, Uprev, M, Cw, inv_d2):
    """One roll-based masked leapfrog step (plain jnp ops): the ONE
    definition of the update used by the Pallas kernel body below and by
    the deep-halo jnp fallback (parallel.deep_halo.make_wave_deep_sweep).
    Roll wraparound only ever feeds edge cells, which M==0 / Cw==0 hold
    bitwise fixed. Returns the advanced (U, U_prev) pair.
    """
    lap = None
    for ax in range(U.ndim):
        term = (
            jnp.roll(U, -1, ax) + jnp.roll(U, 1, ax) - 2.0 * U
        ) * inv_d2[ax]
        lap = term if lap is None else lap + term
    return U + M * (U - Uprev) + Cw * lap, U


def _wave_multi_step_kernel(
    U_ref, Uprev_ref, M_ref, Cw_ref, oU_ref, oUprev_ref, *, inv_d2, chunk
):
    """`chunk` leapfrog steps with the state pair VMEM-resident (bf16
    storage upcast to f32 for the whole chunk — one rounding per chunk).

    Equal-spacing A-form (r4, the wave edition of the diffusion kernel's
    prologue-hoisted refactoring): with one shared inv = inv_d2[ax] the
    update U⁺ = U + M∘(U−U⁻) + Cw∘∇²U distributes to

        U⁺ = A∘U + c∘S − M∘U⁻,   c = Cw·inv,  A = 1 + M − 2·ndim·c,
        S  = Σ_ax (roll(U,-1,ax) + roll(U,+1,ax))

    — A and c hoisted into a once-per-launch prologue, ~3 fewer VPU ops
    per step than the direct form. The Dirichlet hold stays bitwise:
    held cells have M==0, Cw==0 ⇒ c==0, A==1 ⇒ U⁺ = U − 0·U⁻ = U.
    Short chunks keep the direct form (the prologue would not amortize);
    unequal spacing keeps it too (per-axis coefficients would need
    ndim+1 extra arrays for a smaller saving).
    """
    U0, Uprev0, M, Cw = _upcast_for_compute(
        U_ref[:], Uprev_ref[:], M_ref[:], Cw_ref[:]
    )
    if chunk >= 4 and all(inv == inv_d2[0] for inv in inv_d2):
        ndim = U0.ndim
        c = Cw * inv_d2[0]
        A = 1.0 + M - (2.0 * ndim) * c

        def body(_, s):
            U, Uprev = s
            S = None
            for ax in range(ndim):
                r = jnp.roll(U, -1, ax) + jnp.roll(U, 1, ax)
                S = r if S is None else S + r
            return A * U + c * S - M * Uprev, U

    else:

        def body(_, s):
            return masked_leapfrog_step(s[0], s[1], M, Cw, inv_d2)

    U, Uprev = lax.fori_loop(0, chunk, body, (U0, Uprev0), unroll=True)
    oU_ref[:] = U.astype(oU_ref.dtype)
    oUprev_ref[:] = Uprev.astype(oUprev_ref.dtype)


def interior_mask(shape, dtype):
    """1.0 on interior cells, exactly 0.0 on the global Dirichlet edge
    (the shared edge detection of ops.pallas_kernels.edge_mask)."""
    from rocm_mpi_tpu.ops.pallas_kernels import edge_mask

    return jnp.where(
        edge_mask(shape), jnp.zeros(shape, dtype), jnp.ones(shape, dtype)
    )


def wave_multi_step_masked(U, Uprev, M, Cw, spacing, n_steps: int,
                           interpret=None):
    """`n_steps` unrolled leapfrog steps on a VMEM-resident state pair with
    caller-supplied interior mask `M` and masked coefficient `Cw` (dt²·c²
    where the cell updates, exactly 0.0 where held) — the wave analog of
    ops.pallas_kernels.multi_step_cm, and the local compute of wave deep-
    halo sweeps (parallel.deep_halo.make_wave_deep_sweep): the caller pads
    the blocks and zeroes M/Cw on ghost/Dirichlet cells; `n_steps` must
    not exceed the ghost width. Returns the advanced (U, U_prev) pair.
    """
    if interpret is None:
        interpret = _interpret_default()
    if not _supports_compiled(U.dtype) and not interpret:
        raise TypeError(f"Mosaic does not support {U.dtype}")
    if not (U.shape == Uprev.shape == M.shape == Cw.shape):
        raise ValueError(
            f"shape mismatch: U {U.shape}, Uprev {Uprev.shape}, "
            f"M {M.shape}, Cw {Cw.shape}"
        )
    nbytes = _compute_nbytes(U)
    if nbytes > _VMEM_BLOCK_BUDGET_BYTES // 2:
        raise ValueError(
            f"block of {nbytes} bytes (f32 compute width) exceeds the "
            f"wave VMEM-resident budget ({_VMEM_BLOCK_BUDGET_BYTES // 2})"
        )
    inv_d2 = tuple(1.0 / (float(d) * float(d)) for d in spacing)
    kernel = functools.partial(
        _wave_multi_step_kernel, inv_d2=inv_d2, chunk=int(n_steps)
    )
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kernel,
        out_shape=(_out_struct(U.shape, U), _out_struct(U.shape, U)),
        in_specs=[vmem, vmem, vmem, vmem],
        out_specs=(vmem, vmem),
        interpret=interpret,
        name="wave_multi_step_masked",
    )(U, Uprev, M, Cw)


def wave_multi_step(
    U, Uprev, C2, dt, spacing, n_steps, chunk=None, interpret=None,
    warn_on_cap=True, config=None,
):
    """Advance a *single-shard* leapfrog state `n_steps` barely leaving
    VMEM — the wave edition of ops.pallas_kernels.fused_multi_step (same
    schedule, chunk, and compile-time constraints; see its docstring).
    Returns the advanced (U, U_prev) pair. `chunk` must divide `n_steps`
    when both are static; the outer trip count is dynamic — and for a
    TRACED `n_steps` divisibility cannot be checked at trace time: the
    trip count floors, silently dropping any `n_steps % chunk` remainder
    (ADVICE r3). Callers with dynamic step counts must guarantee
    divisibility themselves, as run_vmem_resident does via gcd. The kernel
    holds 4 field-sized arrays (U, U⁻, M, Cw), so admission is gated on
    half the diffusion kernel's VMEM budget. `config="auto"` fills an
    unset `chunk` from the tuning cache (op "wave.vmem_loop"); a miss
    keeps the default chunk policy, bitwise-identically.
    """
    from rocm_mpi_tpu.ops.pallas_kernels import resolve_step_chunk

    if interpret is None:
        interpret = _interpret_default()
    if not _supports_compiled(U.dtype) and not interpret:
        raise TypeError(f"Mosaic does not support {U.dtype}")
    if config == "auto" and chunk is None and isinstance(n_steps, int):
        # Static step counts only: a tuned chunk is a PREFERENCE the
        # divisibility contract still governs (gcd, mirroring the
        # default policy); with a traced n the caller's own guarantee
        # covers only the default chunk, so auto stays hands-off.
        from rocm_mpi_tpu.tuning import resolve as tuning_resolve

        from rocm_mpi_tpu.ops.pallas_kernels import adoptable_vmem_chunk

        tuned = tuning_resolve.resolve("wave.vmem_loop", U.shape, U.dtype)
        if tuned and adoptable_vmem_chunk(tuned.get("chunk")):
            import math

            chunk = math.gcd(n_steps, tuned["chunk"]) or None
    elif config not in (None, "default", "auto"):
        raise ValueError(
            f"config must be None, 'default' or 'auto', got {config!r}"
        )
    nbytes = _compute_nbytes(U)
    if nbytes > _VMEM_BLOCK_BUDGET_BYTES // 2:
        raise ValueError(
            f"field of {nbytes} bytes (f32 compute width) exceeds the "
            f"wave VMEM-resident budget ({_VMEM_BLOCK_BUDGET_BYTES // 2}); "
            "use the per-step path"
        )
    chunk = resolve_step_chunk(n_steps, chunk, nbytes, warn_on_cap)
    M = interior_mask(U.shape, U.dtype)
    Cw = (float(dt) * float(dt)) * C2 * M
    return lax.fori_loop(
        0,
        n_steps // chunk,
        lambda _, s: wave_multi_step_masked(
            s[0], s[1], M, Cw, spacing, chunk, interpret=interpret
        ),
        (U, Uprev),
    )

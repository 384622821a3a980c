"""Pallas TPU kernels for the shallow-water workload (third model family —
no reference analog; the reference ships exactly one physics model).

Physics: the linearized shallow-water equations in a closed basin,
discretized on an Arakawa-C-style staggered grid where every field keeps
the SAME array shape (h at cell centers; u_a at the +a face of its cell):

    h' = h  − dt·H·Σ_a ∂a⁻(u_a)        (backward differences)
    u_a' = M_a ∘ (u_a − dt·g·∂a⁺(h'))  (forward differences, updated h)

This forward-backward (symplectic-Euler) pairing of adjoint difference
operators is the classic energy-stable scheme for first-order wave systems.
Unlike the diffusion (one field) and wave (state pair, one exchanged field)
workloads, the SWE state is ndim+1 COUPLED fields whose updates read
neighbors of *different* fields — the case that exercises the framework's
pytree-state halo machinery (parallel.overlap, parallel.deep_halo).

Boundary design — mask-as-data, no `where` in the hot loop: the face mask
M_a is exactly 0.0 on the global high wall (face index n_g−1 along axis a)
and 1.0 elsewhere, so wall-face velocities stay bitwise 0 forever; the low
wall is the zero-ghost convention (u_a[−1] ghosts arrive as zeros,
parallel.halo). Sealed walls give EXACT mass conservation: Σ_core ∂a⁻u_a
telescopes to (wall − wall) = 0, so sum(h) is invariant to fp rounding —
the workload's machine-checkable invariant (tests/test_swe.py), alongside
algebraic time-reversibility (the update has a closed-form inverse).

The roll form below is exact even ON the global array: jnp.roll wraparound
brings exactly the opposite wall face, which the masks hold at 0 — so one
definition (`rolled_swe_step`) serves the ap variant, the VMEM-resident
multi-step kernel, and the deep-halo sweep fallback. `masked_swe_step` is
the one-chip perf step: the same update as one row-striped Pallas sweep
of the unpadded state, with zero ghosts at the domain edge, reading its
walls from the masks packed into a 1-byte code (`pack_walls`), and the
interior of the 2D sharded hide step, whose boundary slabs
`swe_slab_step` sweeps from their ghosted windows.
"""

from __future__ import annotations

import functools

import jax.numpy as jnp
from jax import lax
from rocm_mpi_tpu.utils.compat import pallas as pl
from rocm_mpi_tpu.utils.compat import pallas_tpu as pltpu

from rocm_mpi_tpu.ops.pallas_kernels import (
    _VMEM_BLOCK_BUDGET_BYTES,
    _compute_itemsize,
    _compute_nbytes,
    _interpret_default,
    _out_struct,
    _stripe_ghost_specs,
    _supports_compiled,
    _tiled_elems,
    _upcast_for_compute,
)


def swe_coeffs(dt, spacing, H, g):
    """Per-axis scalar update coefficients (cH_a, cg_a) = (dt·H/d_a,
    dt·g/d_a) — the only place the physical constants meet the grid."""
    cH = tuple(float(dt) * float(H) / float(d) for d in spacing)
    cg = tuple(float(dt) * float(g) / float(d) for d in spacing)
    return cH, cg


def rolled_swe_step(h, us, Mus, cH, cg):
    """One forward-backward SWE step (plain jnp rolls) — THE single
    definition of the update, shared by the ap variant (global arrays:
    wraparound brings the opposite wall face, held 0 by the masks), the
    VMEM-resident Pallas kernel body, and the deep-halo fallback (padded
    blocks: wraparound feeds only the ghost ring, cropped at sweep end;
    off-domain faces are zeroed by the padded masks).

    `us`/`Mus` are length-ndim sequences; returns (h', us')."""
    div = None
    for a, u in enumerate(us):
        d = cH[a] * (u - jnp.roll(u, 1, a))
        div = d if div is None else div + d
    h = h - div
    us = tuple(
        Mus[a] * (u - cg[a] * (jnp.roll(h, -1, a) - h))
        for a, u in enumerate(us)
    )
    return h, us


def _swe_padded_math(hp, ups, Mus, cH, cg):
    """The staggered-index C-grid update on width-1-padded values — THE
    one copy of the coupled slicing arithmetic, shared by the jnp padded
    form and the Pallas kernel body. Returns the (h', u0', …) core tuple.

    h' is computed on the core-plus-high-pad box (one extra cell on the
    high side of every axis) so the forward differences the velocity
    updates need never require a second exchange — one ghost exchange of
    the full state advances the whole coupled step."""
    ndim = hp.ndim
    ext = tuple(slice(1, None) for _ in range(ndim))
    div = None
    for a, up in enumerate(ups):
        hi = [slice(1, None)] * ndim
        lo = [slice(1, None)] * ndim
        lo[a] = slice(0, -1)
        d = cH[a] * (up[tuple(hi)] - up[tuple(lo)])
        div = d if div is None else div + d
    h_ext = hp[ext] - div
    base = tuple(slice(0, -1) for _ in range(ndim))
    h_core = h_ext[base]
    core = tuple(slice(1, -1) for _ in range(ndim))
    outs = [h_core]
    for a, up in enumerate(ups):
        sh = [slice(0, -1)] * ndim
        sh[a] = slice(1, None)
        dh = h_ext[tuple(sh)] - h_core
        outs.append(Mus[a] * (up[core] - cg[a] * dh))
    return tuple(outs)


def swe_step_padded(Sp, Mus, consts, dt, spacing):
    """Candidate SWE update for every core cell of a width-1-padded block
    (pure jnp) — the framework's padded contract (docs/ADDING_A_MODEL.md
    §1) for a PYTREE state: `Sp = (hp, u0p, …)` are all width-1 padded
    (ghosts from exchange_halo), `Mus` are core-shaped face masks,
    `consts = (H, g)`. Returns the (h', u0', …) core tuple
    (_swe_padded_math has the index-arithmetic story)."""
    hp, *ups = Sp
    H, g = consts
    cH, cg = swe_coeffs(dt, spacing, H, g)
    return _swe_padded_math(hp, ups, Mus, cH, cg)


def _swe_kernel_whole(*refs, ndim, cH, cg):
    """Whole-block Pallas twin of swe_step_padded: refs are
    [hp, u0p…, Mu0…, oh, ou0…] (padded state, core masks, core outs).
    The index arithmetic is the shared _swe_padded_math on the
    VMEM-resident values (consts pre-divided into cH/cg by the caller)."""
    n_state = ndim + 1
    pad_in = refs[:n_state]
    mask_in = refs[n_state:n_state + ndim]
    outs = refs[n_state + ndim:]
    vals = _upcast_for_compute(*[r[:] for r in pad_in + mask_in])
    Sp, Mus = vals[:n_state], vals[n_state:]
    hp, *ups = Sp
    res = _swe_padded_math(hp, ups, Mus, cH, cg)
    for o_ref, r in zip(outs, res):
        o_ref[:] = r.astype(o_ref.dtype)


def swe_step_padded_pallas(Sp, Mus, consts, dt, spacing, interpret=None):
    """Pallas whole-block form of the padded SWE step (the sharded perf
    kernel, and the 3D hide step's on every region). Falls back to the
    identical-semantics jnp padded form for
    blocks beyond the VMEM budget and for dtypes Mosaic cannot compile
    (f64 on a real chip) — same policy as the wave workload's kernel
    (wave_step_padded_pallas: the non-flagship models prefer a slower
    correct path over a crash)."""
    hp = Sp[0]
    ndim = hp.ndim
    if interpret is None:
        interpret = _interpret_default()
    # 2·(ndim+1) padded + ndim mask arrays resident at f32 compute width.
    nbytes = (3 * ndim + 2) * _compute_nbytes(Mus[0])
    if (not _supports_compiled(hp.dtype) and not interpret) or (
        nbytes > _VMEM_BLOCK_BUDGET_BYTES
    ):
        return swe_step_padded(Sp, Mus, consts, dt, spacing)
    H, g = consts
    cH, cg = swe_coeffs(dt, spacing, H, g)
    kernel = functools.partial(_swe_kernel_whole, ndim=ndim, cH=cH, cg=cg)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    core_shape = Mus[0].shape
    out_sd = tuple(
        _out_struct(core_shape, hp) for _ in range(ndim + 1)
    )
    outs = pl.pallas_call(
        kernel,
        out_shape=out_sd,
        in_specs=[vmem] * (2 * ndim + 1),
        out_specs=(vmem,) * (ndim + 1),
        interpret=interpret,
        name="swe_step_padded_pallas",
    )(*Sp, *Mus)
    return tuple(outs)


# ---------------------------------------------------------------------------
# One-chip per-step sweep: the unpadded state in row stripes, one kernel a
# step — the perf step of an unsharded grid, with no exchange and no pad.
# ---------------------------------------------------------------------------

# Rows of a neighbour block: one f32 sublane tile, the least block a
# stripe's row above or below can come in.
_SWE_GHOST_ROWS = 8
# Rows of the wall code's sublane tile: a 1-byte block's stripe height is
# a multiple of it on the chip.
_SWE_CODE_ROWS = 32
# The tallest stripe: at 12288 columns a v5e steps 6.14 / 6.00 / 5.93 /
# 5.86 ms at 64 / 96 / 128 / 192 rows (fewer ghost rows read), but the
# kernel's own scoped VMEM grows faster than its stripe (6 MiB at 128
# rows, 18 at 192 over 8192 columns, past _SWE_SCRATCH_BYTES).
_SWE_STRIPE_ROWS = 128
# Scoped VMEM the striped sweep's pipeline buffers may take: per stripe
# 6 stripe-high f32 blocks (h, u, v in; h', u', v' out), 1 stripe-high
# 1-byte block (the wall code) and 4 ghost blocks, each double-buffered;
# 128-row stripes up to 12288 columns. The compiler adds its own scratch
# on top (_SWE_SCRATCH_BYTES); v5e has 128 MiB of VMEM.
_SWE_STRIPE_BUFFER_BYTES = 80 * 1024 * 1024
_SWE_SCRATCH_BYTES = 16 * 1024 * 1024


def pack_walls(Mus):
    """The face masks as one 1-byte wall code: bit a is set where face
    mask a is nonzero (open), clear where it is 0 (a wall). Masks are
    exactly 0.0 or 1.0 (models.swe.face_masks), so the code holds them
    whole; `masked_swe_step` reads it in their place, once a step, at a
    byte a point instead of 4·ndim."""
    code = None
    for a, M in enumerate(Mus):
        bit = (M != 0).astype(jnp.int8) << a
        code = bit if code is None else code | bit
    return code


def unpack_walls(walls, ndim, dtype):
    """pack_walls' inverse: the `ndim` face masks, 0.0 or 1.0 in
    `dtype`, of the wall code `walls`."""
    return tuple(((walls >> a) & 1).astype(dtype) for a in range(ndim))


def _swe_striped_kernel(h_ref, hd_ref, uu_ref, u_ref, ud_ref, v_ref, vd_ref,
                        w_ref, oh_ref, ou_ref, ov_ref, *, cH, cg, tm, n0):
    """One forward-backward step of output stripe i, rows [i·tm, i·tm+tm).

    h' is computed on the stripe and the g rows below it (the ghost
    blocks hd, ud, vd), so u' takes its forward difference of h' inside
    the stripe, as _swe_padded_math does on its core-plus-high-pad box;
    u's backward difference takes its row above from uu. Ghost blocks
    past the domain's edge read zeros, and so do the shifted reads across
    the lane axis's edges: the zero-ghost convention. h' one column
    past the domain is a zero-ghost cell's, cH1·v of the last column, as
    h' one row past it is, so an open high face steps as in the padded
    form (a wall face is 0 there, so h' past it is too). Where the last
    stripe is partial, its rows past the domain read zeros too, since
    the row below the last feeds h' past the domain. The walls come from
    the wall code w (pack_walls): u' and v' are selected to 0 where
    bit 0 or bit 1 is clear, so a wall face is exactly 0."""
    g = _SWE_GHOST_ROWS
    i = pl.program_id(0)
    first = i == 0
    last = i == pl.num_programs(0) - 1
    zg = jnp.zeros(hd_ref.shape, hd_ref.dtype)
    H = jnp.concatenate([h_ref[:], jnp.where(last, zg, hd_ref[:])], 0)
    U = jnp.concatenate([jnp.where(first, zg, uu_ref[:]), u_ref[:],
                         jnp.where(last, zg, ud_ref[:])], 0)
    V = jnp.concatenate([v_ref[:], jnp.where(last, zg, vd_ref[:])], 0)
    H, U, V = _upcast_for_compute(H, U, V)
    code = w_ref[:].astype(jnp.int32)

    # Mosaic refuses a slice of an iota: each shape takes its own.
    def iota(shape, axis):
        return lax.broadcasted_iota(jnp.int32, shape, axis)

    if n0 % tm:
        inside = i * tm + iota(H.shape, 0) < n0
        H, V = jnp.where(inside, H, 0.0), jnp.where(inside, V, 0.0)
        U = jnp.where(i * tm - g + iota(U.shape, 0) < n0, U, 0.0)
    n1 = H.shape[1]
    # h' on rows [i·tm, i·tm+tm+g): the operation order of rolled_swe_step.
    U_c = U[g:]
    U_lo = jnp.roll(U, 1, 0)[g:]
    V_lo = jnp.where(iota(V.shape, 1) == 0, 0.0, jnp.roll(V, 1, 1))
    Hn = H - (cH[0] * (U_c - U_lo) + cH[1] * (V - V_lo))
    h_new = Hn[:tm]
    h_dn = jnp.roll(Hn, -1, 0)[:tm]
    V_c = V[:tm]
    h_rt = jnp.where(iota(h_new.shape, 1) == n1 - 1, cH[1] * V_c,
                     jnp.roll(h_new, -1, 1))
    oh_ref[:] = h_new.astype(oh_ref.dtype)
    ou_ref[:] = jnp.where((code & 1) != 0,
                          U_c[:tm] - cg[0] * (h_dn - h_new),
                          0.0).astype(ou_ref.dtype)
    ov_ref[:] = jnp.where((code & 2) != 0,
                          V_c - cg[1] * (h_rt - h_new),
                          0.0).astype(ov_ref.dtype)


def _swe_stripe_height(n0: int, row_bytes: int, code_row_bytes: int) -> int:
    """The tallest stripe, up to _SWE_STRIPE_ROWS (a multiple of the wall
    code's tile rows, and no taller than the grid rounded up to them),
    whose pipeline buffers fit _SWE_STRIPE_BUFFER_BYTES; taller stripes
    read their ghost rows less often."""
    g, c = _SWE_GHOST_ROWS, _SWE_CODE_ROWS
    fit = ((_SWE_STRIPE_BUFFER_BYTES // 2 - 4 * g * row_bytes)
           // (6 * row_bytes + code_row_bytes))
    return max(c, min(fit // c * c, _SWE_STRIPE_ROWS, -(-n0 // c) * c))


def masked_swe_step(h, us, walls, cH, cg, interpret=None):
    """One forward-backward step of an unsharded state as one Pallas
    program, named `masked_swe_step`: the perf step of a one-chip grid.

    `walls` is the face masks' 1-byte wall code (pack_walls), made once
    a call outside the step loop: the kernel reads a byte a point for
    the walls, not two f32 masks. Any 0/1 mask pattern is honoured as
    handed, an interior wall or all ones included. The 2D state is read
    once and written once in row stripes (_swe_stripe_height; the last
    may be partial), with no pad and no exchange: each stripe takes its
    neighbour rows from 8-row blocks of the stripes beside it, the ghost
    blocks of pallas_kernels.masked_step (_stripe_ghost_specs), and its
    walls from the code's stripe (_swe_striped_kernel). Other ranks,
    grids of fewer than 8 rows and f64 on the chip take rolled_swe_step,
    the same update in jnp on the global arrays, with the masks unpacked
    from the code. Returns (h', us')."""
    if interpret is None:
        interpret = _interpret_default()
    g = _SWE_GHOST_ROWS
    if (h.ndim != 2 or h.shape[0] < g
            or (not _supports_compiled(h.dtype) and not interpret)):
        Mus = unpack_walls(walls, h.ndim, h.dtype)
        return rolled_swe_step(h, us, Mus, cH, cg)
    for arr in (*us, walls):
        if arr.shape != h.shape:
            raise ValueError(
                f"all SWE fields share one shape: h {h.shape} vs {arr.shape}"
            )
    n0, n1 = h.shape
    row_bytes = _compute_itemsize(h.dtype) * _tiled_elems((n1,))
    code_row_bytes = walls.dtype.itemsize * _tiled_elems((n1,))
    tm = _swe_stripe_height(n0, row_bytes, code_row_bytes)
    core, up, dn = _stripe_ghost_specs(tm, g, n0, (n1,))
    kernel = functools.partial(_swe_striped_kernel, cH=tuple(cH),
                               cg=tuple(cg), tm=tm, n0=n0)
    buffers = 2 * ((6 * tm + 4 * g) * row_bytes + tm * code_row_bytes)
    u, v = us
    h, u, v = pl.pallas_call(
        kernel,
        out_shape=tuple(_out_struct(h.shape, h) for _ in range(3)),
        grid=(-(-n0 // tm),),
        in_specs=[core, dn, up, core, dn, core, dn, core],
        out_specs=(core, core, core),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=buffers + _SWE_SCRATCH_BYTES),
        interpret=interpret,
        name="masked_swe_step",
    )(h, h, u, u, u, v, v, walls)
    return h, (u, v)


# ---------------------------------------------------------------------------
# Boundary slabs of the 2D sharded hide step: the padded windows the
# overlap splice assembles (halo.ghosted_window), in row stripes.
# ---------------------------------------------------------------------------

# The tallest slab stripe: a column slab (thousands of rows, a few
# columns) takes 1024-row stripes, a row slab (b rows, a whole shard
# wide) one stripe of a multiple of _SWE_CODE_ROWS.
_SWE_SLAB_ROWS = 1024
# Scoped VMEM the slab sweep's pipeline buffers may take (the compiler's
# scratch, _SWE_SCRATCH_BYTES, on top): a 32-row slab of 12288 columns
# holds 3 padded stripes, 3 ghost blocks, 3 outputs and the wall code,
# double buffered, 22 MB.
_SWE_SLAB_BUFFER_BYTES = 32 * 1024 * 1024


def _swe_slab_kernel(h_ref, hg_ref, u_ref, ug_ref, v_ref, vg_ref, w_ref,
                     oh_ref, ou_ref, ov_ref, *, cH, cg):
    """One forward-backward step of output stripe i of a slab, core rows
    [i·tm, i·tm+tm): padded rows [i·tm, i·tm+tm+2) of each window are the
    stripe's own block and the first two rows of the ghost block below
    it, the walls the code's stripe, and the update _swe_padded_math, as
    swe_step_padded computes it. Rows past the window feed only rows past
    the slab, which are dropped."""
    ext = _upcast_for_compute(*(
        jnp.concatenate([c[:], g[:2]], 0)
        for c, g in ((h_ref, hg_ref), (u_ref, ug_ref), (v_ref, vg_ref))))
    hp, up, vp = ext
    Mus = unpack_walls(w_ref[:].astype(jnp.int32), 2, hp.dtype)
    for o_ref, r in zip((oh_ref, ou_ref, ov_ref),
                        _swe_padded_math(hp, (up, vp), Mus, cH, cg)):
        o_ref[:] = r.astype(o_ref.dtype)


def _swe_slab_stripe_height(m: int, pad_row_bytes: int, core_row_bytes: int,
                            code_row_bytes: int) -> int:
    """The tallest stripe, up to _SWE_SLAB_ROWS (a multiple of the wall
    code's tile rows, and no taller than the slab rounded up to them),
    whose pipeline buffers fit _SWE_SLAB_BUFFER_BYTES."""
    g, c = _SWE_GHOST_ROWS, _SWE_CODE_ROWS
    per_row = 3 * pad_row_bytes + 3 * core_row_bytes + code_row_bytes
    fit = (_SWE_SLAB_BUFFER_BYTES // 2 - 3 * g * pad_row_bytes) // per_row
    return max(c, min(fit // c * c, _SWE_SLAB_ROWS, -(-m // c) * c))


def swe_slab_step(Sp, walls, cH, cg, interpret=None):
    """One forward-backward step of a boundary slab of the 2D sharded
    `hide` step, as one Pallas program named `swe_slab_step`.

    `Sp = (hp, up, vp)` are the slab's width-1-padded windows as
    halo.ghosted_window assembles them (the ghosts the neighbours sent,
    zeros at a global face) and `walls` the slab's cells of the wall
    code (pack_walls). Returns the (h', u', v') of the slab's cells,
    swe_step_padded's update with the masks read from the code: u' and
    v' read h' one cell after, which the window's ghost ring gives. The
    slab is swept in row stripes (_swe_slab_stripe_height), each with
    the 8-row block below it for its two rows past the stripe, as
    masked_swe_step takes its ghost rows. f64 on the chip takes the same
    update in jnp."""
    hp = Sp[0]
    if interpret is None:
        interpret = _interpret_default()
    core = walls.shape
    if not _supports_compiled(hp.dtype) and not interpret:
        return _swe_padded_math(hp, Sp[1:], unpack_walls(walls, 2, hp.dtype),
                                cH, cg)
    g = _SWE_GHOST_ROWS
    m, n = core
    pad_row_bytes = _compute_itemsize(hp.dtype) * _tiled_elems((n + 2,))
    core_row_bytes = _compute_itemsize(hp.dtype) * _tiled_elems((n,))
    code_row_bytes = walls.dtype.itemsize * _tiled_elems((n,))
    tm = _swe_slab_stripe_height(m, pad_row_bytes, core_row_bytes,
                                 code_row_bytes)
    r, n_ghost = tm // g, -(-(m + 2) // g)
    stripe = pl.BlockSpec((tm, n + 2), lambda i: (i, 0),
                          memory_space=pltpu.VMEM)
    ghost = pl.BlockSpec((g, n + 2),
                         lambda i: (jnp.minimum((i + 1) * r, n_ghost - 1), 0),
                         memory_space=pltpu.VMEM)
    out = pl.BlockSpec((tm, n), lambda i: (i, 0), memory_space=pltpu.VMEM)
    buffers = 2 * (tm * (3 * pad_row_bytes + 3 * core_row_bytes
                         + code_row_bytes) + 3 * g * pad_row_bytes)
    kernel = functools.partial(_swe_slab_kernel, cH=tuple(cH), cg=tuple(cg))
    outs = pl.pallas_call(
        kernel,
        out_shape=tuple(_out_struct(core, hp) for _ in range(3)),
        grid=(-(-m // tm),),
        in_specs=[stripe, ghost] * 3 + [out],
        out_specs=(out, out, out),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=buffers + _SWE_SCRATCH_BYTES),
        interpret=interpret,
        name="swe_slab_step",
    )(Sp[0], Sp[0], Sp[1], Sp[1], Sp[2], Sp[2], walls)
    return tuple(outs)


def _swe_multi_step_kernel(*refs, ndim, cH, cg, chunk):
    """`chunk` forward-backward steps with the whole state VMEM-resident
    (bf16 storage upcast to f32 for the chunk — one rounding per chunk,
    the storage-only-bf16 policy of the diffusion/wave multi-step
    kernels). refs = [h, u0…, Mu0…, oh, ou0…], all same-shape."""
    n_state = ndim + 1
    ins = refs[:n_state + ndim]
    outs = refs[n_state + ndim:]
    vals = _upcast_for_compute(*[r[:] for r in ins])
    h0, us0, Mus = vals[0], vals[1:n_state], vals[n_state:]

    def body(_, s):
        return rolled_swe_step(s[0], s[1], Mus, cH, cg)

    h, us = lax.fori_loop(0, chunk, body, (h0, tuple(us0)), unroll=True)
    outs[0][:] = h.astype(outs[0].dtype)
    for a, u in enumerate(us):
        outs[a + 1][:] = u.astype(outs[a + 1].dtype)


def swe_multi_step_masked(h, us, Mus, cH, cg, n_steps: int, interpret=None):
    """`n_steps` unrolled SWE steps on a VMEM-resident state with
    caller-supplied face masks — the SWE analog of
    ops.pallas_kernels.multi_step_cm / wave_kernels.wave_multi_step_masked,
    and the local compute of SWE deep-halo sweeps: the caller pads the
    blocks and zeroes the masks on wall/off-domain faces; `n_steps` must
    not exceed the ghost width (the light-cone bound). Returns (h, us)."""
    if interpret is None:
        interpret = _interpret_default()
    if not _supports_compiled(h.dtype) and not interpret:
        raise TypeError(f"Mosaic does not support {h.dtype}")
    ndim = h.ndim
    if len(us) != ndim or len(Mus) != ndim:
        raise ValueError(
            f"need ndim={ndim} velocity fields and masks, got "
            f"{len(us)} and {len(Mus)}"
        )
    for arr in (*us, *Mus):
        if arr.shape != h.shape:
            raise ValueError(
                f"all SWE fields share one shape: h {h.shape} vs {arr.shape}"
            )
    # 2·(ndim+1) state + ndim masks resident at f32 compute width.
    nbytes = (3 * ndim + 2) * _compute_nbytes(h)
    if nbytes > _VMEM_BLOCK_BUDGET_BYTES:
        raise ValueError(
            f"state of {nbytes} bytes (f32 compute width) exceeds the "
            f"VMEM-resident budget ({_VMEM_BLOCK_BUDGET_BYTES})"
        )
    kernel = functools.partial(
        _swe_multi_step_kernel, ndim=ndim, cH=tuple(cH), cg=tuple(cg),
        chunk=int(n_steps),
    )
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    out_sd = tuple(_out_struct(h.shape, h) for _ in range(ndim + 1))
    outs = pl.pallas_call(
        kernel,
        out_shape=out_sd,
        in_specs=[vmem] * (2 * ndim + 1),
        out_specs=(vmem,) * (ndim + 1),
        interpret=interpret,
        name="swe_multi_step_masked",
    )(h, *us, *Mus)
    return outs[0], tuple(outs[1:])


def swe_multi_step(
    h, us, Mus, dt, spacing, H, g, n_steps, chunk=None, interpret=None,
    warn_on_cap=True, config=None,
):
    """Advance a *single-shard* SWE state `n_steps` barely leaving VMEM —
    the SWE edition of fused_multi_step / wave_multi_step (same chunk
    policy, resolve_step_chunk; same dynamic-n divisibility caveat: a
    TRACED `n_steps` floors the trip count, so callers must guarantee
    `chunk | n_steps` themselves, as run_vmem_resident does via gcd).
    `Mus` must already hold the wall faces (models.swe.face_masks) — on
    the global array the roll wraparound then reads exactly those zeroed
    opposite wall faces, keeping the closed-basin physics exact.
    `config="auto"` fills an unset `chunk` from the tuning cache (op
    "swe.vmem_loop", static n_steps only — gcd'd, same policy as the
    wave/diffusion editions); a miss keeps the defaults bitwise."""
    from rocm_mpi_tpu.ops.pallas_kernels import resolve_step_chunk

    if interpret is None:
        interpret = _interpret_default()
    if not _supports_compiled(h.dtype) and not interpret:
        raise TypeError(f"Mosaic does not support {h.dtype}")
    if config == "auto" and chunk is None and isinstance(n_steps, int):
        from rocm_mpi_tpu.tuning import resolve as tuning_resolve

        from rocm_mpi_tpu.ops.pallas_kernels import adoptable_vmem_chunk

        tuned = tuning_resolve.resolve("swe.vmem_loop", h.shape, h.dtype)
        if tuned and adoptable_vmem_chunk(tuned.get("chunk")):
            import math

            chunk = math.gcd(n_steps, tuned["chunk"]) or None
    elif config not in (None, "default", "auto"):
        raise ValueError(
            f"config must be None, 'default' or 'auto', got {config!r}"
        )
    nbytes = (3 * h.ndim + 2) * _compute_nbytes(h)
    if nbytes > _VMEM_BLOCK_BUDGET_BYTES:
        raise ValueError(
            f"state of {nbytes} bytes (f32 compute width) exceeds the "
            f"VMEM-resident budget ({_VMEM_BLOCK_BUDGET_BYTES}); use the "
            "per-step path"
        )
    chunk = resolve_step_chunk(n_steps, chunk, _compute_nbytes(h),
                               warn_on_cap)
    cH, cg = swe_coeffs(dt, spacing, H, g)
    return lax.fori_loop(
        0,
        n_steps // chunk,
        lambda _, s: swe_multi_step_masked(
            s[0], s[1], Mus, cH, cg, chunk, interpret=interpret
        ),
        (h, tuple(us)),
    )

"""Communication/computation overlap (D8) — the perf_hide rung, working.

Reference intent (/root/reference/scripts/diffusion_2D_perf_hide.jl): split
the update into a boundary frame of width `b_width` computed on a
HIGH-priority HSA queue and an interior computed on a LOW-priority queue,
with `update_halo!` issued between the two waits so the exchange hides
behind interior compute. The shipped code never got there: its active
variant (2) under-covers the frame and skips the halo entirely, and the true
overlap variant (3) is commented "not ready yet" (hide.jl:84-101;
SURVEY.md §3.4 caveat). This module implements variant (3)'s *semantics* —
for any number of dimensions (2D frame, 3D shell) — and lets XLA's
latency-hiding scheduler do the queue juggling:

Per step, inside one shard_map program:
  1. `ppermute` the current field's edge slices to the cartesian neighbors
     (the halo exchange) — depends only on the field's edges;
  2. update the interior region — it reads the UNPADDED local block
     directly (its width-1 stencil window never leaves the shard), so it
     depends on NO ghost value and XLA is free to run the collective and
     the interior compute concurrently (this dataflow independence is the
     whole trick: no user-visible queues, priorities, or signals —
     SURVEY.md §2.2 D8, made explicit rather than left to XLA's
     slice-of-concatenate simplifier);
  3. update the boundary slabs once their ghosts arrive;
  4. write every region's result into one output buffer with
     `lax.dynamic_update_slice` — no per-axis concatenate tree, no
     staging copies; with the masked-coefficient contract (below) held
     cells come back unchanged from the region update itself, so there is
     no trailing whole-shard Dirichlet `jnp.where` either.

Traffic (the A_eff accounting docs/PERF.md formalizes): the old splice
rebuilt the shard through a tree of `jnp.concatenate`s (one staging copy
per axis level) and then paid a whole-shard select; the in-place splice
writes each region exactly once into a buffer XLA can alias with the
input block.

Unlike the reference's two-queue scheme, correctness never rests on manual
signal ordering (hide.jl:69,86-90): the schedule is derived from dataflow,
so there is nothing to race (SURVEY.md §5.2).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from rocm_mpi_tpu import telemetry
from rocm_mpi_tpu.parallel.halo import exchange_halo, exchange_halo_batched
from rocm_mpi_tpu.parallel.mesh import GlobalGrid


def effective_b_width(local_shape, b_width) -> tuple[int, ...]:
    """Clamp the boundary-frame width per axis to at most half the shard
    (the reference's b_width=(32,4) knob, hide.jl:42, made shape-safe).
    A short b_width tuple is extended by repeating its last entry (so the
    2D default applies to 3D grids)."""
    b_width = tuple(b_width)
    if len(b_width) < len(local_shape):
        b_width = b_width + (b_width[-1],) * (len(local_shape) - len(b_width))
    for ln in local_shape:
        if ln < 2:
            raise ValueError(
                f"hide variant needs every shard axis >= 2 cells (local "
                f"shape {tuple(local_shape)}); use variant 'shard' for "
                "degenerate decompositions"
            )
    return tuple(
        max(1, min(int(b), ln // 2)) for b, ln in zip(b_width, local_shape)
    )


def make_overlap_step(
    grid: GlobalGrid,
    padded_update: Callable,
    b_width: tuple[int, ...],
    mask_boundary: bool = True,
    wire_mode: str = "f32",
):
    """Build the shard-local overlap step (any ndim).

    `padded_update(Tp, C, lam, dt, spacing)` is any core-update kernel with
    the padded contract (jnp or Pallas). Returns
    `local_step(Tl, Cl, lam, dt, spacing) -> Tl_new`.

    `Tl` — the exchanged state — may itself be a pytree of same-shaped
    arrays (r4: the shallow-water workload's (h, u, v), whose coupled
    update reads neighbors of every field): each leaf is halo-exchanged
    and region-sliced, `padded_update` receives the padded pytree, returns
    the same-structure core pytree, and the slab/interior splice happens
    leaf-wise. A bare array is the one-leaf case — the diffusion and wave
    callers are unchanged, op for op.

    `Cl` may be any pytree of core-shaped operands (a bare coefficient
    array for the diffusion rungs; a (U_prev, C2) tuple for the wave
    leapfrog; the face masks for SWE) — each leaf is sliced to the region
    and the whole tree is handed to `padded_update` as its second
    argument. Aux operands are read core-only, never exchanged.

    `wire_mode` selects the exchange's on-wire slab precision
    (parallel/wire.py): the per-step overlap program is stateless, so
    only "f32" (bitwise-unchanged) and "bf16" are legal here — the
    exchange decodes every received slab back to the buffer dtype
    BEFORE it reaches the slab updates, so the masked seam (the region
    kernels below) only ever consumes upcast, full-precision-dtype
    ghosts (the GL04 contract).

    `mask_boundary=False` drops the Dirichlet hold entirely: for the
    masked contracts (Cm — the boundary-masked coefficient of
    models.diffusion `_make_masked_step`; the mask-as-data operands of the
    wave and SWE models), held cells already come back unchanged from the
    region update, so any select would be dead work. This is the contract
    every in-repo caller uses. `mask_boundary=True` keeps a hold for
    external padded_updates without a masked form; its edge-cell
    indicators are precomputed at build time (numpy constants closed over
    here — only the ndim scalar `axis_index` compares remain in the traced
    step, not a per-step iota/compare chain).

    The shard is decomposed axis-by-axis into boundary slabs and one
    interior box: axis 0 contributes the first/last `b` rows (full extent
    elsewhere), axis 1 the first/last `b` columns of the remaining middle,
    and so on; the innermost box is the ghost-free interior. Only the
    axis-0/…​ slabs read exchanged ghosts — the interior reads the unpadded
    local block, which is what makes the exchange hideable.
    """
    from rocm_mpi_tpu.parallel import wire

    # Mode validity checked here; the stateful-mode refusal (this
    # program is stateless) fires at trace time inside exchange_halo,
    # so a model whose config carries a deep-only wire mode can still
    # BUILD its per-step variants and run its deep schedule.
    wire.validate_mode(wire_mode)
    bw = effective_b_width(grid.local_shape, b_width)
    splice = _make_region_splice(grid, padded_update, bw, mask_boundary)

    def local_step(Tl, Cpl, lam, dt, spacing):
        if telemetry.enabled():
            # Trace-time: the slab geometry this compiled overlap step
            # uses (the per-leaf halo.exchange byte annotations fire
            # inside exchange_halo below).
            telemetry.annotate(
                "overlap.step", b_width=tuple(int(b) for b in bw),
                leaves=len(jax.tree_util.tree_leaves(Tl)),
                wire=wire_mode,
            )
        # (1) halo exchange of the current state — edge-slice ppermutes,
        # one exchange per state leaf (SWE: 3 fields; diffusion/wave: 1),
        # at the wire mode's on-wire precision (received slabs arrive
        # already decoded to the buffer dtype).
        Tp = jax.tree_util.tree_map(
            lambda t: exchange_halo(t, grid, wire_mode=wire_mode), Tl
        )  # core + 2 per axis
        return splice(Tl, Tp, Cpl, lam, dt, spacing)

    return local_step


def make_batched_overlap_step(
    bgrid,
    padded_update: Callable,
    b_width: tuple[int, ...],
    mask_boundary: bool = False,
    wire_mode: str = "f32",
):
    """The lane-batched overlap step (docs/SERVING.md "The pipeline"):
    the masked-seam hide of `make_overlap_step`, vmapped over the
    leading lane axis of a `BatchedGrid` — the batched serving program
    itself hides its exchange under interior compute, the paper's
    tentpole at batch scale.

    Inside a shard_map over `bgrid.mesh`, `batched_local(Tb_l, Cpl,
    lam, dt, spacing)` takes the local `(local_batch, *local_space)`
    block of `bgrid.spec`-sharded state and the UNBATCHED lane-shared
    aux block. The exchange runs through `exchange_halo_batched`
    (aggregate lane bytes booked on the wire annotation; halo
    collectives stay strictly per-space-axis — nothing ever permutes
    over `batch`), and the region splice is vmapped per lane: the
    interior boxes still read the UNPADDED lane block, so their
    dataflow independence from the (lane-batched) collective — the
    whole hide trick — survives the vmap unchanged.

    Stateless wire modes only (f32/bf16), enforced by
    `exchange_halo_batched`. `mask_boundary` defaults to False — every
    in-repo batched caller is on the Cm masked-coefficient contract."""
    from rocm_mpi_tpu.parallel import wire

    wire.validate_mode(wire_mode)
    space = bgrid.space
    bw = effective_b_width(space.local_shape, b_width)
    splice = _make_region_splice(space, padded_update, bw, mask_boundary)

    def batched_local(Tb_l, Cpl, lam, dt, spacing):
        if telemetry.enabled():
            telemetry.annotate(
                "overlap.step.batched",
                b_width=tuple(int(b) for b in bw),
                lanes=int(jax.tree_util.tree_leaves(Tb_l)[0].shape[0]),
                leaves=len(jax.tree_util.tree_leaves(Tb_l)),
                wire=wire_mode,
            )
        Tp_b = jax.tree_util.tree_map(
            lambda t: exchange_halo_batched(t, bgrid,
                                            wire_mode=wire_mode),
            Tb_l,
        )
        return jax.vmap(
            lambda Tl, Tpl: splice(Tl, Tpl, Cpl, lam, dt, spacing)
        )(Tb_l, Tp_b)

    return batched_local


def _make_region_splice(
    grid: GlobalGrid,
    padded_update: Callable,
    bw: tuple[int, ...],
    mask_boundary: bool,
):
    """Build `splice(Tl, Tp, Cpl, lam, dt, spacing) -> Tl_new`: the
    boundary-slab/interior decomposition and the in-place DUS splice of
    `make_overlap_step`, factored over an ALREADY-exchanged padded
    state `Tp` so the single-lane and lane-batched steps share one
    seam (the batched edition exchanges through
    `exchange_halo_batched` and vmaps this per lane)."""
    local = grid.local_shape
    ndim = grid.ndim

    def boxes(axis, prefix):
        """Enumerate the region boxes (per-axis (lo, hi) core ranges) —
        the same decomposition the concatenate tree used to assemble,
        computed once at build time."""
        if axis == ndim:
            return [tuple(prefix)]  # the interior box
        n, b = local[axis], bw[axis]
        rest = [(0, local[a]) for a in range(axis + 1, ndim)]
        out = [
            tuple(prefix + [(0, b)] + rest),  # lo slab: reads ghosts
            tuple(prefix + [(n - b, n)] + rest),  # hi slab: reads ghosts
        ]
        if n - 2 * b > 0:
            out[1:1] = boxes(axis + 1, prefix + [(b, n - b)])
        return out

    all_boxes = boxes(0, [])

    def ghost_free(bounds):
        """True when the box's width-1 stencil window never leaves the
        unpadded shard — it can (and must, for overlap) read `Tl`."""
        return all(
            lo >= 1 and hi <= local[a] - 1
            for a, (lo, hi) in enumerate(bounds)
        )

    if mask_boundary:
        # Build-time edge indicators (numpy): cell lies on the shard face
        # that COULD be a global-domain face. The traced step only adds
        # the per-axis scalar axis_index compares.
        edge_lo, edge_hi = [], []
        for ax in range(ndim):
            lo = np.zeros(local, bool)
            hi = np.zeros(local, bool)
            lo[tuple(0 if a == ax else slice(None) for a in range(ndim))] = True
            hi[tuple(-1 if a == ax else slice(None) for a in range(ndim))] = True
            edge_lo.append(lo)
            edge_hi.append(hi)

    def splice(Tl, Tp, Cpl, lam, dt, spacing):
        def region(bounds):
            """Candidate update of the core box given by `bounds`. Slab
            boxes read the padded state; ghost-free boxes (the interior)
            read the raw block — no dataflow edge to the collective."""
            core_idx = tuple(slice(lo, hi) for lo, hi in bounds)
            cp = jax.tree_util.tree_map(lambda a: a[core_idx], Cpl)
            if ghost_free(bounds):
                raw_idx = tuple(slice(lo - 1, hi + 1) for lo, hi in bounds)
                tp = jax.tree_util.tree_map(lambda a: a[raw_idx], Tl)
            else:
                pad_idx = tuple(slice(lo, hi + 2) for lo, hi in bounds)
                tp = jax.tree_util.tree_map(lambda a: a[pad_idx], Tp)
                # The barrier keeps the TPU compiler (libtpu 0.0.34) from
                # fusing a slab kernel's output into its DUS consumer:
                # with two such slabs that fusion pass aborts the
                # process (tests/test_tpu_compile.py pins the 2x2 hide
                # step). Only libtpu needs it; it is kept on every backend
                # so the CPU tests run the program the chip compiles. Its
                # cost is not measured (PERF.md, open questions).
                return lax.optimization_barrier(
                    padded_update(tp, cp, lam, dt, spacing)
                )
            return padded_update(tp, cp, lam, dt, spacing)

        # (2)+(3) region updates, (4) spliced in place: every box is
        # written exactly once, so the seed buffer's values never survive
        # — XLA may alias it with Tl's storage (dead after the exchange),
        # and each region+DUS link lowers to an in-place update-slice
        # fusion (observed on the CPU backend) instead of the old concat
        # tree's whole-shard staging copies.
        new = Tl
        for bounds in all_boxes:
            res = region(bounds)
            origin = tuple(lo for lo, _ in bounds)
            new = jax.tree_util.tree_map(
                lambda o, r: lax.dynamic_update_slice(o, r, origin),
                new, res,
            )
        if not mask_boundary:
            return new
        # Dirichlet hold for unmasked padded_updates: global-domain edge
        # cells keep their old values (edge indicators are build-time
        # constants; only the axis_index compares are traced per step).
        mask = None
        for ax, name in enumerate(grid.axis_names):
            idx = lax.axis_index(name)
            m = ((idx == 0) & edge_lo[ax]) | (
                (idx == grid.dims[ax] - 1) & edge_hi[ax]
            )
            mask = m if mask is None else mask | m
        return jax.tree_util.tree_map(
            lambda old, nw: jnp.where(mask, old, nw), Tl, new
        )

    return splice

"""Deep-halo sweeps: width-k ghost exchange every k steps.

The multi-chip form of temporal blocking, and the TPU-first endpoint of the
reference's communication-ladder: where the reference hides a width-1 halo
exchange behind interior compute every step
(/root/reference/scripts/diffusion_2D_perf_hide.jl:94-101, its intended
variant (3)), the deep-halo sweep removes most exchanges altogether —
each device receives a k-wide ghost region once, then advances its block k
steps entirely locally (the ghost light cone keeps the core exact; stale
ghost cells are cropped at sweep end). Communication drops from one
latency-bound message per neighbor per step to one k-times-larger message
per neighbor per k steps — the shape ICI wants: fewer, larger transfers,
k× less exposed latency. Same total exchanged volume, identical math
(fp-reordering aside) to k per-step updates.

Correctness argument (the same light-cone bound as the HBM temporal
blocking in ops.pallas_kernels._tb_kernel): after s local steps, values at
ghost depth ≥ s+1 from the core are stale (the outermost ghost layer is
either roll-wraparound garbage or held, depending on the local kernel —
both contaminate inward one cell per step); for s ≤ k neither reaches the
core. Dirichlet global-boundary cells are held by a zero update
coefficient, and off-domain ghost cells (domain edge) hold zeros with a
zero coefficient — the zero-ghost convention used framework-wide.

Time-invariant operands are exchanged ONCE per compiled advance, not once
per sweep: every builder returns a `DeepSchedule(prepare, sweep, k)`
where `prepare` runs the ghost exchange + masking of the loop-invariant
operands (diffusion's Cp→Cm, the wave's C2→(M, Cw), the SWE face masks)
as its own shard_map program whose *block-padded* output the caller
hoists outside the `fori_loop` — the carried loop state stays the bare
field(s), and the per-sweep program exchanges exactly the state. (The old
form re-exchanged the coefficient inside every sweep; the perf gate's
traffic audit, docs/PERF.md, is what made that cost visible.)
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax.numpy as jnp
from jax import lax

from rocm_mpi_tpu.utils.compat import shard_map

from rocm_mpi_tpu import telemetry
from rocm_mpi_tpu.parallel import wire
from rocm_mpi_tpu.parallel.halo import exchange_halo
from rocm_mpi_tpu.parallel.mesh import GlobalGrid


class DeepSchedule(NamedTuple):
    """A deep-halo schedule: `prepare(*aux)` exchanges/masks the
    loop-invariant operands once (returning block-padded global arrays —
    each shard's slice is its k-padded block), `sweep(state…, prepared)`
    advances the state k steps with one state exchange. Callers jit
    `prepare` outside their step loop and carry only the state.

    `rebuild(new_grid)` re-derives the SAME schedule (physics constants,
    depth, local form, wire mode) for a new decomposition — the
    elastic-resume path (rebuild_for_mesh below): ghost widths, padded
    block geometry, face masks, and the VMEM-vs-HBM local-kernel routing
    all depend on the shard shape, so nothing built for the old mesh may
    be reused.

    `wire_mode` is the state exchange's on-wire precision
    (parallel/wire.py; the loop-invariant `prepare` exchange always
    ships full precision — it runs once per compiled advance, so its
    bytes are not the term that grows with the mesh, and coefficient
    error would bias every step). For the stateful modes
    (int8/int8_delta) `init_wire(dtype)` builds the flat zero wire-state
    tuple and `sweep` grows a trailing wire-state argument + return:
    `sweep(state…, prepared, wire_state) -> (state…, wire_state)` — the
    drivers carry it alongside the field(s). `init_wire` is None for
    stateless modes and the sweep signature is unchanged."""

    prepare: Callable
    sweep: Callable
    k: int
    rebuild: Callable | None = None
    wire_mode: str = "f32"
    init_wire: Callable | None = None


def _validate_depth(grid: GlobalGrid, k: int, label: str = "sweep depth"):
    if k < 1:
        raise ValueError(f"{label} k must be >= 1, got {k}")
    if any(k > ln for ln in grid.local_shape):
        raise ValueError(
            f"{label} {k} exceeds a local shard extent "
            f"{grid.local_shape}; ghost slices need width <= shard"
        )


def padded_hold_mask(shape, grid: GlobalGrid, width: int):
    """Boolean mask over a width-`width` padded block (inside shard_map):
    True where the cell must NOT update — global Dirichlet boundary cells
    and off-domain ghost cells, located by global index."""
    mask = None
    for ax, name in enumerate(grid.axis_names):
        ln = grid.local_shape[ax]
        n_g = grid.global_shape[ax]
        gidx = (
            lax.axis_index(name) * ln
            + lax.broadcasted_iota(jnp.int32, shape, ax)
            - width
        )
        m = (gidx <= 0) | (gidx >= n_g - 1)
        mask = m if mask is None else (mask | m)
    return mask


def padded_update_coefficient(Cp_padded, grid: GlobalGrid, width: int,
                              lam, dt):
    """Masked dt·λ/Cp for a width-`width` padded block (inside shard_map).

    Zero where the cell must not update: global Dirichlet boundary cells,
    and off-domain ghost cells (where the exchanged `Cp_padded` is itself
    zero — guarded so the division cannot produce inf).
    """
    mask = padded_hold_mask(Cp_padded.shape, grid, width)
    safe = jnp.where(Cp_padded == 0, jnp.ones_like(Cp_padded), Cp_padded)
    return jnp.where(mask, jnp.zeros_like(Cp_padded), (dt * lam) / safe)


def resolve_deep_config(grid: GlobalGrid, dtype,
                        config: str | None) -> dict:
    """The tuned deep-halo configuration for this shard/topology:
    ``{"k": int | None, "wire_mode": str | None}`` — None fields mean
    "use the model's default policy". The deep edition of the
    `config="auto"` seam: consults the tuning cache (tuning/resolve.py,
    op "diffusion.deep", keyed by the LOCAL shard shape and mesh dims —
    the winner shifts with both) and re-validates the cached depth
    against this grid's shard extents, because a cache entry tuned on
    one mesh can outlive a reshard that shrank the shards
    (`_validate_depth`'s own rule, applied silently: a stale depth falls
    back to the default policy rather than crashing an auto run). The
    wire mode rides the same entry (the PR-12 wire axis) — resolve's
    sanitizer already dropped unknown modes, and the gate/validate CLI
    is the loud half that rejects an uncertified or over-ladder one."""
    nothing = {"k": None, "wire_mode": None}
    if config in (None, "default"):
        return nothing
    if config != "auto":
        raise ValueError(
            f"config must be None, 'default' or 'auto', got {config!r}"
        )
    import jax

    if jax.process_count() > 1:
        # Multi-controller: each process resolves from its own cache
        # file, and ranks disagreeing on k (or on the wire mode — a
        # bf16 sender into an f32 receiver is a dtype-mismatched
        # collective) build schedules with MISMATCHED collectives — a
        # distributed hang, not an error. The default policy is
        # deterministic on every rank; auto stays hands-off until a
        # broadcast-consistent resolve exists.
        return nothing
    from rocm_mpi_tpu.tuning import resolve as tuning_resolve

    tuned = tuning_resolve.resolve(
        "diffusion.deep", grid.local_shape, dtype, topology=grid.dims
    )
    if not tuned:
        return nothing
    out = dict(nothing)
    if tuned.get("k"):
        k = int(tuned["k"])
        if k >= 1 and all(k <= ln for ln in grid.local_shape):
            out["k"] = k
    if tuned.get("wire_mode"):
        out["wire_mode"] = str(tuned["wire_mode"])
    return out


def resolve_deep_k(grid: GlobalGrid, dtype, config: str | None) -> int | None:
    """The tuned sweep depth alone (resolve_deep_config's k field) —
    the pre-wire-axis spelling, kept for existing callers."""
    return resolve_deep_config(grid, dtype, config)["k"]


def rebuild_for_mesh(sched: DeepSchedule, new_grid: GlobalGrid,
                     dims=None, devices=None) -> DeepSchedule:
    """Re-derive `sched` for a new decomposition of the same global
    domain (docs/RESILIENCE.md "Elastic recovery"). `new_grid` is the
    rebuilt GlobalGrid (mesh.rebuild_for_mesh output), or the OLD grid
    together with `dims`/`devices` to rebuild here. Depth validation is
    the builder's own (_validate_depth): a mesh grown so far that k
    exceeds a shard extent fails loudly, exactly as a fresh build would."""
    if sched.rebuild is None:
        raise ValueError(
            "this DeepSchedule predates the rebuild path (built by hand?) "
            "— reconstruct it with its make_*_deep_sweep builder"
        )
    if dims is not None or devices is not None:
        from rocm_mpi_tpu.parallel import mesh as _mesh

        new_grid = _mesh.rebuild_for_mesh(new_grid, dims=dims,
                                          devices=devices)
    return sched.rebuild(new_grid)


def make_deep_sweep(grid, k: int, lam, dt, spacing,
                    local_form: str = "auto",
                    wire_mode: str = "f32") -> DeepSchedule:
    """Build the diffusion DeepSchedule: `prepare(Cp)` -> block-padded Cm
    (ONE width-k Cp exchange per compiled advance), `sweep(T, Cm)` -> T
    advanced k steps with one width-k T exchange (at `wire_mode`
    precision on the wire; stateful modes grow the sweep signature —
    DeepSchedule docstring has the contract).

    The local k-step kernel is the same unrolled roll-based Pallas program
    as the single-chip VMEM-resident path (ops.pallas_kernels.multi_step_cm)
    — the deep-halo design makes every chip's inner loop identical to the
    fastest single-chip loop, with communication only at sweep boundaries.
    Shards too large for VMEM route to the temporal-blocked HBM sweep
    (multi_step_cm_hbm; k ≤ 16 with a depth-dependent stripe geometry,
    gated on the Mosaic compile envelope — tb_slab_fits): the same
    schedule at every scale — exchange once, advance k steps locally,
    crop. `local_form="jnp"` forces the any-shape XLA fallback — the form
    whose compiled byte counts the perf traffic gate audits on CPU
    (rocm_mpi_tpu/perf/traffic.py); "auto" is the production routing.

    `grid` may be a `mesh.BatchedGrid` (space×batch, docs/SERVING.md):
    the sweep then advances `(batch, *space)` lane-batched state —
    `prepare` takes the UNBATCHED space-shaped Cp every lane shares
    (physics is a bin-key field: one coefficient serves the whole
    batch), the local k-step body is vmapped over the leading lane
    axis, and the halo collectives stay per-space-axis. Batched sweeps
    pin the jnp local form (Pallas-under-vmap routing is not in the
    audited envelope) and the stateless wire modes (f32/bf16).
    """
    from rocm_mpi_tpu.parallel.mesh import BatchedGrid

    batched = isinstance(grid, BatchedGrid)
    space = grid.space if batched else grid
    _validate_depth(space, k, "sweep depth")
    wire.validate_mode(wire_mode)
    stateful_wire = wire.is_stateful(wire_mode)
    if local_form not in ("auto", "jnp"):
        raise ValueError(f"local_form must be 'auto' or 'jnp', got {local_form!r}")
    if batched:
        if stateful_wire:
            raise ValueError(
                f"wire_mode {wire_mode!r} is stateful; batched deep sweeps "
                "support the stateless modes (f32/bf16) only"
            )
        # The vmapped local body stays on the any-shape XLA form: the
        # Pallas kernels' batching path is untested/unaudited here, and
        # a crashed batched sweep serves no tenant.
        local_form = "jnp"
    from rocm_mpi_tpu.ops.pallas_kernels import (
        _TB_MAX_STEPS,
        _VMEM_BLOCK_BUDGET_BYTES,
        _compute_nbytes,
        multi_step_cm,
        multi_step_cm_hbm,
        tb_geometry,
        tb_slab_fits,
    )

    core = tuple(slice(k, -k) for _ in range(space.ndim))
    inner = tuple(slice(1, -1) for _ in range(space.ndim))
    inv_d2 = tuple(1.0 / (float(d) * float(d)) for d in spacing)

    def jnp_k_steps(Tp, Cm):
        # Any-shape/any-k fallback: the padded-slice stencil + an in-place
        # `dynamic_update_slice` of the advanced inner box (the outermost
        # ghost layer is held — same light-cone contamination geometry as
        # the Pallas kernels' roll wraparound, and no whole-block roll
        # staging copies). Slower than temporal blocking but never
        # shape-constrained — the HBM kernel's stripe divisibility, k <= 16
        # bound, and compile-envelope gate do not always survive run_deep's
        # depth degradation (effective_block_steps), and a crashed sweep is
        # strictly worse than a slower one.
        ndim = Tp.ndim
        for _ in range(k):
            lap = None
            for ax in range(ndim):
                hi = tuple(
                    slice(2, None) if a == ax else slice(1, -1)
                    for a in range(ndim)
                )
                lo = tuple(
                    slice(None, -2) if a == ax else slice(1, -1)
                    for a in range(ndim)
                )
                term = (Tp[hi] - 2.0 * Tp[inner] + Tp[lo]) * inv_d2[ax]
                lap = term if lap is None else lap + term
            Tp = lax.dynamic_update_slice(
                Tp, Tp[inner] + Cm[inner] * lap, (1,) * ndim
            )
        return Tp

    def local_prepare(Cpl):
        Cpp = exchange_halo(Cpl, space, width=k)
        return padded_update_coefficient(Cpp, space, k, lam, dt)

    def tb_ok(Tp):
        n0p = Tp.shape[0]
        return (
            k <= _TB_MAX_STEPS
            and Tp.ndim in (2, 3)
            and tb_slab_fits(k, Tp.shape, Tp.dtype)
            and n0p % tb_geometry(k)[1] == 0
            and (n0p // tb_geometry(k)[1]) >= 2
        )

    def local_sweep(Tl, Cm, *wsl):
        if stateful_wire:
            Tp, ws2 = exchange_halo(Tl, space, width=k, wire_mode=wire_mode,
                                    wire_state=tuple(wsl))
        else:
            Tp = exchange_halo(Tl, space, width=k, wire_mode=wire_mode)
            ws2 = ()
        if local_form == "jnp":
            route = "jnp"
            Tp = jnp_k_steps(Tp, Cm)
        elif _compute_nbytes(Tp) <= _VMEM_BLOCK_BUDGET_BYTES:
            route = "vmem"
            Tp = multi_step_cm(Tp, Cm, spacing, k)
        elif tb_ok(Tp):
            route = "hbm-tb"
            Tp = multi_step_cm_hbm(Tp, Cm, spacing, k)
        else:
            route = "jnp"
            Tp = jnp_k_steps(Tp, Cm)
        if telemetry.enabled():
            # Trace-time: which local kernel this compiled sweep routed to
            # (the halo.exchange byte annotation fired inside exchange_halo).
            telemetry.annotate("deep.sweep", k=k, route=route,
                               steps_per_exchange=k, wire=wire_mode)
        return (Tp[core],) + ws2 if stateful_wire else Tp[core]

    aux_spec = grid.aux_spec if batched else grid.spec

    def prepare(Cp):
        # Batched: Cp is the UNBATCHED space-shaped coefficient every
        # lane shares — same local program, replicated over batch rows.
        return shard_map(
            local_prepare,
            mesh=grid.mesh,
            in_specs=(aux_spec,),
            out_specs=aux_spec,
            check_vma=False,
        )(Cp)

    if stateful_wire:

        def sweep(T, Cm, wire_state):
            ws = tuple(wire_state)
            outs = shard_map(
                local_sweep,
                mesh=grid.mesh,
                in_specs=(grid.spec, aux_spec) + (grid.spec,) * len(ws),
                out_specs=(grid.spec,) * (1 + len(ws)),
                check_vma=False,
            )(T, Cm, *ws)
            return outs[0], tuple(outs[1:])

    else:
        if batched:
            import jax

            from rocm_mpi_tpu.parallel.halo import exchange_halo_batched

            def sweep_body(Tb_l, Cm):
                # The exchange runs through exchange_halo_batched so
                # the trace-time `halo.exchange.batched` annotation
                # books the TRUE lane-aggregate wire bytes — vmapping
                # exchange_halo would annotate a single lane's slab
                # and under-report the wire by the lane count. Only
                # the k-step local kernel is vmapped (shared Cm rides
                # unbatched in its closure).
                Tp_b = exchange_halo_batched(Tb_l, grid, width=k,
                                             wire_mode=wire_mode)
                if telemetry.enabled():
                    telemetry.annotate(
                        "deep.sweep", k=k, route="jnp",
                        steps_per_exchange=k, wire=wire_mode,
                        lanes=int(Tb_l.shape[0]),
                    )
                return jax.vmap(
                    lambda Tp: jnp_k_steps(Tp, Cm)[core]
                )(Tp_b)
        else:
            sweep_body = local_sweep

        def sweep(T, Cm):
            return shard_map(
                sweep_body,
                mesh=grid.mesh,
                in_specs=(grid.spec, aux_spec),
                out_specs=grid.spec,
                check_vma=False,
            )(T, Cm)

    return DeepSchedule(
        prepare, sweep, k,
        rebuild=lambda g: make_deep_sweep(g, k, lam, dt, spacing,
                                          local_form=local_form,
                                          wire_mode=wire_mode),
        wire_mode=wire_mode,
        init_wire=(
            (lambda dtype: wire.init_exchange_state(grid, k, wire_mode,
                                                    dtype))
            if stateful_wire else None
        ),
    )


def padded_face_mask(shape, grid: GlobalGrid, axis: int, width: int, dtype):
    """Face mask for the u_axis field over a width-`width` padded block
    (inside shard_map): exactly 0.0 on the global high wall face (global
    index n_g−1 along `axis`) and on off-domain ghost faces along `axis`,
    1.0 elsewhere. Zeroed wall faces seal the closed basin — off-domain
    ghost values then cannot influence any in-domain cell no matter how
    many local steps a sweep takes (flux across a wall is identically 0),
    which is what lets the SWE deep sweep evolve its ghost ring freely and
    crop it. Off-domain faces along OTHER axes need no zeroing: their
    influence would have to cross that axis's wall to reach the domain."""
    name = grid.axis_names[axis]
    ln = grid.local_shape[axis]
    n_g = grid.global_shape[axis]
    gidx = (
        lax.axis_index(name) * ln
        + lax.broadcasted_iota(jnp.int32, shape, axis)
        - width
    )
    invalid = (gidx >= n_g - 1) | (gidx < 0)
    return jnp.where(
        invalid, jnp.zeros(shape, dtype), jnp.ones(shape, dtype)
    )


def make_swe_deep_sweep(grid: GlobalGrid, k: int, dt, spacing, H,
                        g, wire_mode: str = "f32") -> DeepSchedule:
    """Deep-halo DeepSchedule for the shallow-water workload:
    `prepare(h)` -> the block-padded face masks (geometry-only; `h` just
    donates dtype and sharding — computed ONCE per compiled advance),
    `sweep(h, us, Mus_padded)` -> (h, us) advanced k steps with ONE
    width-k ghost exchange of the whole ndim+1-field coupled state (same
    light-cone argument as make_deep_sweep: the forward-backward update
    moves information one cell per step in each direction, so width-k
    ghosts keep the core exact for k steps).

    Local compute: the VMEM-resident masked multi-step kernel
    (ops.swe_kernels.swe_multi_step_masked) when the padded state fits,
    else the identical-semantics jnp roll fallback (rolled_swe_step — the
    one definition of the update)."""
    _validate_depth(grid, k, "sweep depth")
    wire.validate_mode(wire_mode)
    stateful_wire = wire.is_stateful(wire_mode)
    from rocm_mpi_tpu.ops.pallas_kernels import (
        _VMEM_BLOCK_BUDGET_BYTES,
        _compute_nbytes,
    )
    from rocm_mpi_tpu.ops.swe_kernels import (
        rolled_swe_step,
        swe_coeffs,
        swe_multi_step_masked,
    )

    ndim = grid.ndim
    nfields = ndim + 1  # h + one velocity per axis, all exchanged
    core = tuple(slice(k, -k) for _ in range(ndim))
    cH, cg = swe_coeffs(dt, spacing, H, g)
    padded_local = tuple(ln + 2 * k for ln in grid.local_shape)
    # Flat wire-state arrays per exchanged field (wire.state_arity per
    # slab, 2 slabs per axis).
    per_field = wire.state_arity(wire_mode) * 2 * ndim

    def jnp_k_steps(h, us, Mus):
        for _ in range(k):
            h, us = rolled_swe_step(h, us, Mus, cH, cg)
        return h, us

    def local_prepare(hl):
        return tuple(
            padded_face_mask(padded_local, grid, a, k, hl.dtype)
            for a in range(ndim)
        )

    def _exchange(f, wsl, i):
        if not stateful_wire:
            return exchange_halo(f, grid, width=k, wire_mode=wire_mode), ()
        return exchange_halo(
            f, grid, width=k, wire_mode=wire_mode,
            wire_state=tuple(wsl[i * per_field:(i + 1) * per_field]),
        )

    def local_sweep(hl, *rest):
        uls, Mus = rest[:ndim], rest[ndim:2 * ndim]
        wsl = rest[2 * ndim:]
        hp, ws_h = _exchange(hl, wsl, 0)
        ups, ws_us = [], ()
        for i, u in enumerate(uls):
            up, ws_u = _exchange(u, wsl, 1 + i)
            ups.append(up)
            ws_us += ws_u
        ups = tuple(ups)
        if (3 * ndim + 2) * _compute_nbytes(hp) <= _VMEM_BLOCK_BUDGET_BYTES:
            h2, us2 = swe_multi_step_masked(hp, ups, Mus, cH, cg, k)
        else:
            h2, us2 = jnp_k_steps(hp, ups, Mus)
        return (
            (h2[core],) + tuple(u[core] for u in us2) + ws_h + ws_us
        )

    def prepare(h):
        return shard_map(
            local_prepare,
            mesh=grid.mesh,
            in_specs=(grid.spec,),
            out_specs=(grid.spec,) * ndim,
            check_vma=False,
        )(h)

    if stateful_wire:

        def sweep(h, us, Mus_padded, wire_state):
            ws = tuple(wire_state)
            outs = shard_map(
                local_sweep,
                mesh=grid.mesh,
                in_specs=(grid.spec,) * (2 * ndim + 1 + len(ws)),
                out_specs=(grid.spec,) * (ndim + 1 + len(ws)),
                check_vma=False,
            )(h, *us, *Mus_padded, *ws)
            return (
                outs[0], tuple(outs[1:nfields]), tuple(outs[nfields:])
            )

    else:

        def sweep(h, us, Mus_padded):
            outs = shard_map(
                local_sweep,
                mesh=grid.mesh,
                in_specs=(grid.spec,) * (2 * ndim + 1),
                out_specs=(grid.spec,) * (ndim + 1),
                check_vma=False,
            )(h, *us, *Mus_padded)
            return outs[0], tuple(outs[1:])

    return DeepSchedule(
        prepare, sweep, k,
        rebuild=lambda ng: make_swe_deep_sweep(ng, k, dt, spacing, H, g,
                                               wire_mode=wire_mode),
        wire_mode=wire_mode,
        init_wire=(
            (lambda dtype: wire.init_exchange_state(grid, k, wire_mode,
                                                    dtype, fields=nfields))
            if stateful_wire else None
        ),
    )


def make_wave_deep_sweep(grid: GlobalGrid, k: int, dt, spacing,
                         wire_mode: str = "f32") -> DeepSchedule:
    """Deep-halo DeepSchedule for the acoustic-wave workload:
    `prepare(C2)` -> block-padded (M, Cw) — ONE width-k exchange of the
    time-invariant squared wave speed per compiled advance, with the hold
    mask M and the masked coefficient Cw = dt²·c²·M derived in the same
    program — and `sweep(U, Uprev, (M, Cw))` -> (U, Uprev) advanced k
    steps with ONE width-k ghost exchange of the leapfrog state pair (the
    second workload on the flagship multi-chip schedule; same light-cone
    argument as make_deep_sweep, both outputs cropped).

    Local compute: the VMEM-resident masked leapfrog kernel
    (ops.wave_kernels.wave_multi_step_masked) when the padded block fits,
    else an XLA-fused jnp fallback with identical semantics (the wave
    workload is the layering demo — it has no HBM temporal-blocked rung).
    """
    _validate_depth(grid, k, "sweep depth")
    wire.validate_mode(wire_mode)
    stateful_wire = wire.is_stateful(wire_mode)
    from rocm_mpi_tpu.ops.pallas_kernels import (
        _VMEM_BLOCK_BUDGET_BYTES,
        _compute_nbytes,
    )
    from rocm_mpi_tpu.ops.wave_kernels import (
        masked_leapfrog_step,
        wave_multi_step_masked,
    )

    core = tuple(slice(k, -k) for _ in range(grid.ndim))
    inv_d2 = tuple(1.0 / (float(d) * float(d)) for d in spacing)
    dt2 = float(dt) * float(dt)
    per_field = wire.state_arity(wire_mode) * 2 * grid.ndim

    def jnp_k_steps(U, Uprev, M, Cw):
        for _ in range(k):
            U, Uprev = masked_leapfrog_step(U, Uprev, M, Cw, inv_d2)
        return U, Uprev

    def local_prepare(C2l):
        C2p = exchange_halo(C2l, grid, width=k)
        hold = padded_hold_mask(C2p.shape, grid, k)
        M = jnp.where(hold, jnp.zeros_like(C2p), jnp.ones_like(C2p))
        return M, dt2 * C2p * M

    def local_sweep(Ul, Upl, M, Cw, *wsl):
        if stateful_wire:
            Up_, ws_u = exchange_halo(
                Ul, grid, width=k, wire_mode=wire_mode,
                wire_state=tuple(wsl[:per_field]),
            )
            Upp, ws_p = exchange_halo(
                Upl, grid, width=k, wire_mode=wire_mode,
                wire_state=tuple(wsl[per_field:]),
            )
        else:
            Up_ = exchange_halo(Ul, grid, width=k, wire_mode=wire_mode)
            Upp = exchange_halo(Upl, grid, width=k, wire_mode=wire_mode)
            ws_u = ws_p = ()
        if 2 * _compute_nbytes(Up_) <= _VMEM_BLOCK_BUDGET_BYTES:
            U2, Up2 = wave_multi_step_masked(Up_, Upp, M, Cw, spacing, k)
        else:
            U2, Up2 = jnp_k_steps(Up_, Upp, M, Cw)
        out = (U2[core], Up2[core])
        return out + ws_u + ws_p if stateful_wire else out

    def prepare(C2):
        return shard_map(
            local_prepare,
            mesh=grid.mesh,
            in_specs=(grid.spec,),
            out_specs=(grid.spec, grid.spec),
            check_vma=False,
        )(C2)

    if stateful_wire:

        def sweep(U, Uprev, prepared, wire_state):
            M, Cw = prepared
            ws = tuple(wire_state)
            outs = shard_map(
                local_sweep,
                mesh=grid.mesh,
                in_specs=(grid.spec,) * (4 + len(ws)),
                out_specs=(grid.spec,) * (2 + len(ws)),
                check_vma=False,
            )(U, Uprev, M, Cw, *ws)
            return outs[0], outs[1], tuple(outs[2:])

    else:

        def sweep(U, Uprev, prepared):
            M, Cw = prepared
            return shard_map(
                local_sweep,
                mesh=grid.mesh,
                in_specs=(grid.spec,) * 4,
                out_specs=(grid.spec, grid.spec),
                check_vma=False,
            )(U, Uprev, M, Cw)

    return DeepSchedule(
        prepare, sweep, k,
        rebuild=lambda g: make_wave_deep_sweep(g, k, dt, spacing,
                                               wire_mode=wire_mode),
        wire_mode=wire_mode,
        init_wire=(
            (lambda dtype: wire.init_exchange_state(grid, k, wire_mode,
                                                    dtype, fields=2))
            if stateful_wire else None
        ),
    )

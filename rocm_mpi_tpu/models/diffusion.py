"""The transient heat-diffusion model — the framework's flagship workload.

One physics model at escalating performance levels, mirroring the
reference's app ladder (SURVEY.md §2.1 C1-C4):

  variant "ap"    — array-programming: global-array flux-form jnp ops; GSPMD
                    auto-partitions and inserts halo comms (C1 analog).
  variant "fused" — single fused jnp stencil, double-buffer-free functional
                    update (C3's math, compiler-scheduled).
  Pallas/overlap variants ("kp", "perf", "hide") are added by
  rocm_mpi_tpu.ops.pallas_kernels / parallel.overlap and registered here.

The hot loop lives *inside* one jitted loop (models/driver.py) — the
TPU-first answer to the reference's per-step `wait(@roc …); update_halo!`
host round-trips (scripts/diffusion_2D_perf.jl:47-52): nothing leaves the
device between tic and toc.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp

from rocm_mpi_tpu.utils.compat import shard_map

from rocm_mpi_tpu.config import DiffusionConfig
from rocm_mpi_tpu.models.driver import Driver, Result, effective_block_steps
from rocm_mpi_tpu.ops.diffusion import (
    gaussian_ic,
    step_flux_form,
    step_fused,
    step_fused_padded,
)
from rocm_mpi_tpu.parallel.halo import exchange_halo, global_boundary_mask
from rocm_mpi_tpu.parallel.mesh import GlobalGrid
from rocm_mpi_tpu.utils import compat
from rocm_mpi_tpu.utils import metrics


def default_deep_depth(local_shape, itemsize: int) -> int:
    """run_deep's automatic sweep depth for a given per-device shard.

    Start from DEFAULT_DEEP_STEPS clamped to the shard extent, then halve
    while the k-padded shard exceeds the VMEM budget but a shallower sweep
    would fit — mid-size shards prefer a shallower VMEM-resident sweep
    over the HBM local sweep (e.g. a 672² f32 shard fits VMEM at k=16 but
    not k=32). Shards that fit at no depth run the temporal-blocked HBM
    local sweep, whose stripe ghosts cap the depth at DEFAULT_TB_STEPS.
    """
    from rocm_mpi_tpu.ops.pallas_kernels import (
        _VMEM_BLOCK_BUDGET_BYTES,
        DEFAULT_DEEP_STEPS,
        DEFAULT_TB_STEPS,
    )

    def padded_bytes(kk):
        b = itemsize
        for ln in local_shape:
            b *= ln + 2 * kk
        return b

    k = min(DEFAULT_DEEP_STEPS, min(local_shape))
    while k > DEFAULT_TB_STEPS and padded_bytes(k) > _VMEM_BLOCK_BUDGET_BYTES:
        k //= 2
    if padded_bytes(k) > _VMEM_BLOCK_BUDGET_BYTES:
        k = min(k, DEFAULT_TB_STEPS)
    return max(1, k)


def _whole_shard_interior(dtype):
    """The hide splice's `interior_update` on the Cm contract:
    `masked_step`, the `perf` step's own sweep, on the whole unpadded
    shard — so no near-whole-shard box is sliced out for a Pallas
    operand and written back (its edge values are wrong where a shard
    face is not a global face; the slabs overwrite them). None for f64,
    which Mosaic cannot compile: its interior box stays sliced, for the
    jnp twin."""
    if jnp.dtype(dtype).itemsize > 4:
        return None
    from rocm_mpi_tpu.ops.pallas_kernels import masked_step

    return lambda Tl, cm, lam, dt, spacing: masked_step(Tl, cm, spacing)


def warn_host_transport_ignored(variant: str, stacklevel: int = 3) -> None:
    """The one warning for halo_transport='host' on a variant that keeps its
    device-side communication (only 'shard' routes to the host-staged
    oracle). Shared so the message can't drift between call sites.
    Default stacklevel attributes to run()'s caller; direct callers pass 2.
    """
    import warnings

    warnings.warn(
        f"halo_transport='host' is not honored by variant {variant!r} — "
        "only variant 'shard' routes to the host-staged oracle stepper; "
        "all other variants keep their device-side communication (GSPMD "
        "or ppermute).",
        stacklevel=stacklevel,
    )


@dataclasses.dataclass
class RunResult(Result):
    T: jax.Array  # final temperature field (global, sharded)

    @property
    def field(self):
        return self.T


class HeatDiffusion(Driver):
    """Heat diffusion on a sharded global grid, with selectable step variant."""

    workload = "diffusion"

    def __init__(
        self,
        config: DiffusionConfig,
        grid: GlobalGrid | None = None,
        devices=None,
    ):
        super().__init__(config, grid, devices)
        grid = self.grid
        if grid.global_shape != config.global_shape:
            raise ValueError(
                f"grid shape {grid.global_shape} != config {config.global_shape}"
            )
        if grid.lengths != config.lengths:
            raise ValueError(
                f"grid lengths {grid.lengths} != config {config.lengths}"
            )
        self._step_fns: dict[str, Callable] = {}
        self._prep_fns: dict[str, Callable] = {}
        self.register_variant("ap", self._make_jnp_step(step_flux_form))
        self.register_variant("fused", self._make_jnp_step(step_fused))
        self.register_variant("shard", self._make_shard_step(step_fused_padded))
        # perf: the reference's fused hand-tuned kernel rung
        # (diffusion_2D_perf.jl) — explicit halo + Pallas stencil kernel,
        # Cm contract: the Dirichlet mask and the dt·λ/Cp divide live in a
        # coefficient prepared once per run, so each step is one kernel.
        from rocm_mpi_tpu.ops.pallas_kernels import kp_step_padded

        self.register_variant("perf", *self._make_masked_step())
        # kp: the kernel-programming teaching rung (diffusion_2D_kp.jl) —
        # three separate Pallas kernels per step, staggered-grid shapes.
        # 2D-only, like the reference's kp app. check_vma off:
        # interpret-mode pallas_call (CPU tests) emits constants with empty
        # vma that trip jax 0.9's varying-axes checker.
        if self.grid.ndim == 2:
            self.register_variant(
                "kp", self._make_shard_step(kp_step_padded, check_vma=False)
            )
        # hide: comm/compute overlap (diffusion_2D_perf_hide.jl's intended
        # variant (3), working) — boundary slabs + overlapped halo; N-D.
        self.register_variant("hide", *self._make_hide_step())

    # ---- state ----------------------------------------------------------

    def init_state(self):
        """(T, Cp) on-device, sharded over the grid mesh.

        T₀ = centered Gaussian via global cell-center coordinates — each
        device materializes its shard of the global IC, as each reference
        rank does through x_g/y_g (diffusion_2D_ap.jl:28). Cp = Cp0·ones
        (ap.jl:25).
        """
        cfg, grid = self.config, self.grid
        dtype = cfg.jax_dtype

        @functools.partial(jax.jit, out_shardings=grid.sharding)
        def make_T():
            coords = grid.coord_mesh(dtype=dtype)
            return gaussian_ic(coords, cfg.lengths, dtype=dtype)

        @functools.partial(jax.jit, out_shardings=grid.sharding)
        def make_Cp():
            return jnp.full(grid.global_shape, cfg.cp0, dtype=dtype)

        return make_T(), make_Cp()

    def state_and_aux(self):
        T, Cp = self.init_state()
        return (T,), Cp

    def _result(self, state, **window) -> RunResult:
        return RunResult(T=state[0], **window)

    # ---- variants -------------------------------------------------------

    def register_variant(
        self, name: str, step_fn: Callable, prepare: Callable | None = None
    ):
        """step_fn(T, C, lam, dt, spacing, grid) -> new T.

        `prepare(Cp, lam, dt) -> C` (optional) builds the loop-invariant
        coefficient handed to every step — traced once per jitted program,
        OUTSIDE the time loop (e.g. the Cm masked coefficient of the perf
        rung). Without it, C is Cp itself.
        """
        self._step_fns[name] = step_fn
        if prepare is not None:
            self._prep_fns[name] = prepare
        else:
            self._prep_fns.pop(name, None)

    @property
    def variants(self) -> tuple[str, ...]:
        return tuple(self._step_fns)

    def _get_step(self, variant: str) -> Callable:
        try:
            return self._step_fns[variant]
        except KeyError:
            raise ValueError(
                f"unknown variant {variant!r} for a {self.grid.ndim}D grid; "
                f"available: {', '.join(self.variants)}"
            ) from None

    def _make_jnp_step(self, raw_step):
        def step(T, Cp, lam, dt, spacing, grid):
            del grid  # global formulation: GSPMD handles the decomposition
            return raw_step(T, Cp, lam, dt, spacing)

        return step

    def _make_shard_step(self, padded_update, check_vma: bool = True):
        """Explicit-decomposition step: shard_map + ppermute halo exchange.

        The manual counterpart of "ap": each device exchanges width-1 ghosts
        with its cartesian neighbors (exchange_halo = update_halo! analog),
        applies `padded_update` to its block, and Dirichlet-masks global
        boundary cells. This is the structure the perf/hide ladder builds on.
        """

        wire_mode = self.config.wire_mode

        def step(T, Cp, lam, dt, spacing, grid):
            def local_step(Tl, Cpl):
                Tp = exchange_halo(Tl, grid, wire_mode=wire_mode)
                new = padded_update(Tp, Cpl, lam, dt, spacing)
                return jnp.where(global_boundary_mask(grid), Tl, new)

            return shard_map(
                local_step,
                mesh=grid.mesh,
                in_specs=(grid.spec, grid.spec),
                out_specs=grid.spec,
                check_vma=check_vma,
            )(T, Cp)

        return step

    def _cm_prepare(self):
        """prepare(Cp, lam, dt) -> Cm: the masked coefficient of the Cm
        contract — (dt·λ)/Cp on updating cells, exactly 0.0 on global
        Dirichlet boundary cells — computed once per jitted program."""
        grid = self.grid

        def prepare(Cp, lam, dt):
            def local(Cpl):
                z = jnp.zeros_like(Cpl)
                return jnp.where(
                    global_boundary_mask(grid), z, (dt * lam) / Cpl
                )

            return shard_map(
                local, mesh=grid.mesh, in_specs=(grid.spec,),
                out_specs=grid.spec,
            )(Cp)

        return prepare

    def _make_masked_step(self):
        """perf rung, Cm contract (VERDICT r2 ask #1): `prepare` folds the
        Dirichlet mask and the (dt·λ)/Cp divide into one masked coefficient
        computed once per run, so the per-step program is exactly one
        Pallas kernel (plus the halo exchange when sharded) — the
        reference's per-step schedule (perf.jl:47-52) without its per-step
        divide + where-mask op chain. f64 runs interpret-mode off-TPU
        (tests); on TPU the Cm kernels raise for f64, as the unmasked
        Pallas path did.
        """
        from rocm_mpi_tpu.ops.pallas_kernels import fused_step_cm, masked_step

        grid = self.grid
        prepare = self._cm_prepare()

        if grid.nprocs == 1:
            # Unsharded: no neighbors, the block edge IS the global
            # boundary — one kernel per step, no exchange, no pad.
            def step(T, Cm, lam, dt, spacing, grid_):
                return masked_step(T, Cm, spacing)

            return step, prepare

        wire_mode = self.config.wire_mode

        def step(T, Cm, lam, dt, spacing, grid_):
            def local(Tl, Cml):
                Tp = exchange_halo(Tl, grid, wire_mode=wire_mode)
                return fused_step_cm(Tp, Cml, spacing)

            return shard_map(
                local, mesh=grid.mesh, in_specs=(grid.spec, grid.spec),
                out_specs=grid.spec, check_vma=False,
            )(T, Cm)

        return step, prepare

    def _step(self, variant: str):
        """(`step(state, C) -> state`, `prepare(Cp) -> C` or None): a
        registered variant in the schedule layer's form (models/driver.py)."""
        cfg, grid = self.config, self.grid
        step = self._get_step(variant)
        prep = self._prep_fns.get(variant)
        dt = cfg.jax_dtype(cfg.dt)
        return (
            lambda s, C: (step(s[0], C, cfg.lam, dt, cfg.spacing, grid),),
            None if prep is None else (lambda Cp: prep(Cp, cfg.lam, dt)),
        )

    def step_fn(self, variant: str):
        """jitted single step (T, Cp) -> T (no donation; compile-check safe)."""
        step, prep = self._step(variant)

        @jax.jit
        def one_step(T, Cp):
            return step((T,), Cp if prep is None else prep(Cp))[0]

        return one_step

    def prepared_step_fn(self, variant: str, donate: bool = False):
        """(jitted steady-state step(T, C) -> T, jitted prepare(Cp) -> C):
        the per-step program with the loop-invariant coefficient ALREADY
        prepared — exactly the program the multi-step drivers execute per
        iteration, which is what the perf traffic gate audits
        (rocm_mpi_tpu/perf/traffic.py): a step_fn-style program would
        charge the once-per-run prepare to every step.

        `donate=True` donates T — the drivers' steady-state aliasing
        (their loop carry reuses the field buffer), which is what lets
        XLA update the exchanged buffer in place instead of inserting a
        defensive copy. Callers must then rebind T from the result."""
        step, prep = self._step(variant)

        @jax.jit
        def prepare(Cp):
            return Cp if prep is None else prep(Cp)

        @functools.partial(jax.jit, donate_argnums=(0,) if donate else ())
        def one_step(T, C):
            return step((T,), C)[0]

        return one_step, prepare

    def _make_hide_step(self):
        """Overlap step (parallel.overlap): Pallas slabs and a whole-shard
        `masked_step` interior for f32/bf16, jnp slabs and a sliced jnp
        interior for f64 (Mosaic has no f64) — BOTH on the Cm contract,
        so no caller pays a trailing whole-shard Dirichlet select.
        Returns (step, prepare)."""
        from rocm_mpi_tpu.parallel.overlap import make_overlap_step

        cfg, grid = self.config, self.grid
        compiled_dtype = jnp.dtype(cfg.jax_dtype).itemsize <= 4
        if grid.nprocs == 1:
            # No neighbors → nothing to hide; the boundary/interior strip
            # bookkeeping is pure overhead (measured r1: 8.2 vs 6.3 µs/step
            # at 252²). Route to the same masked per-step program as perf,
            # so hide == perf bit-identically on one device — the
            # reference's variant (2)/(3) distinction only exists once
            # communication exists. (f64 keeps the jnp shard step: Mosaic
            # has no f64, and the jnp path serves TPU parity runs.)
            if compiled_dtype:
                return self._make_masked_step()
            return self._make_shard_step(step_fused_padded), None
        # Cm contract on the strip ladder: the mask+divide live in the
        # prepared coefficient, each region update is one kernel (Pallas
        # for f32/bf16, the bitwise-identical jnp twin for f64), and held
        # cells come back unchanged from the update itself — the trailing
        # whole-shard select the old f64 path paid is dead work the Cm
        # zeros already guarantee (mask_boundary=False everywhere).
        if compiled_dtype:
            from rocm_mpi_tpu.ops.pallas_kernels import fused_step_cm as _cm_kernel
        else:
            from rocm_mpi_tpu.ops.diffusion import step_cm_padded as _cm_kernel

        pu = lambda tp, cm, lam, dt, spacing: _cm_kernel(tp, cm, spacing)
        local = make_overlap_step(
            grid, pu, cfg.b_width, mask_boundary=False,
            wire_mode=cfg.wire_mode,
            interior_update=_whole_shard_interior(cfg.jax_dtype),
        )
        prepare = self._cm_prepare()

        def step(T, C, lam, dt, spacing, grid_):
            return shard_map(
                lambda Tl, Cl: local(Tl, Cl, lam, dt, spacing),
                mesh=grid.mesh,
                in_specs=(grid.spec, grid.spec),
                out_specs=grid.spec,
                check_vma=False,
            )(T, C)

        return step, prepare

    # ---- multi-tenant batching (docs/SERVING.md) ------------------------

    def _make_batched_step(self, bgrid, variant: str):
        """(`step(state, C) -> state`, prepare-or-None) over
        `(batch, *space)` lane-batched state (C is the UNBATCHED
        space-shaped coefficient every lane shares — physics is a bin-key
        field, docs/SERVING.md; `prepare(Cp) -> C` is the loop-invariant
        coefficient transform, traced once per jitted program like the
        unbatched variants' prep). "shard" runs the explicit exchange
        machinery — shard_map over the space×batch mesh, the per-lane
        local step vmapped over the leading lane axis, halo collectives
        per-space-axis only; "hide" the lane-batched comm/compute
        overlap (make_batched_overlap_step on the Cm contract — the
        exchange hides under the vmapped interior compute);
        "ap"/"fused" vmap the global-array step and let GSPMD partition
        the batched array. Every form is bitwise-equal per lane to the
        unbatched variant (the serving layer's parity contract)."""
        from rocm_mpi_tpu.ops.diffusion import step_fused_padded
        from rocm_mpi_tpu.parallel.halo import exchange_halo_batched

        cfg = self.config
        space = bgrid.space
        dt = cfg.jax_dtype(cfg.dt)

        if variant in ("ap", "fused"):
            raw = (step_flux_form if variant == "ap" else step_fused)

            def step(s, C):
                return (jax.vmap(
                    lambda T: raw(T, C, cfg.lam, dt, cfg.spacing)
                )(s[0]),)

            return step, None

        if variant == "hide":
            return self._make_batched_hide_step(bgrid)

        if variant != "shard":
            raise ValueError(
                f"batched advance supports variants 'shard', 'hide', "
                f"'ap', 'fused'; got {variant!r} (the Pallas rungs "
                "are single-lane)"
            )

        wire_mode = cfg.wire_mode

        def lane_local(Tb_l, Cl):
            # Tb_l: (local_batch, *local_space); Cl: local space block.
            Tp = exchange_halo_batched(Tb_l, bgrid, wire_mode=wire_mode)
            mask = global_boundary_mask(space)

            def lane(Tl, Tpl):
                with compat.set_xla_metadata(layer="lane_step"):
                    new = step_fused_padded(Tpl, Cl, cfg.lam, dt,
                                            cfg.spacing)
                    return jnp.where(mask, Tl, new)

            return jax.vmap(lane)(Tb_l, Tp)

        def step(s, C):
            return (shard_map(
                lane_local,
                mesh=bgrid.mesh,
                in_specs=(bgrid.spec, bgrid.aux_spec),
                out_specs=bgrid.spec,
                check_vma=False,
            )(s[0], C),)

        return step, None

    def _make_batched_hide_step(self, bgrid):
        """The lane-batched overlap step (docs/SERVING.md "The
        pipeline"): the masked-seam hide vmapped over the lane axis —
        one width-1 exchange of the whole lane batch whose collectives
        are dataflow-independent of every interior box, so XLA hides
        the (lane-aggregate) exchange under the vmapped interior
        compute. The slabs run the Cm jnp twin
        (`ops.diffusion.step_cm_padded`) on every dtype — the same
        kernel the single-lane f64 hide and the CPU traffic audit
        lower, bitwise-equal to the Pallas Cm form — with the
        mask+divide folded into the prepared coefficient (`prepare`),
        so held cells come back unchanged from the region updates
        themselves. The interior is the single-lane hide's: a
        whole-shard `masked_step` per lane for the compiled dtypes,
        the sliced jnp twin for f64."""
        from rocm_mpi_tpu.ops.diffusion import step_cm_padded
        from rocm_mpi_tpu.parallel.overlap import make_batched_overlap_step

        cfg = self.config
        space = bgrid.space
        dt = cfg.jax_dtype(cfg.dt)
        pu = lambda tp, cm, lam, dt_, spacing: step_cm_padded(
            tp, cm, spacing
        )
        batched_local = make_batched_overlap_step(
            bgrid, pu, cfg.b_width, mask_boundary=False,
            wire_mode=cfg.wire_mode,
            interior_update=_whole_shard_interior(cfg.jax_dtype),
        )

        def prepare(Cp):
            def local(Cpl):
                z = jnp.zeros_like(Cpl)
                return jnp.where(
                    global_boundary_mask(space), z, (dt * cfg.lam) / Cpl
                )

            return shard_map(
                local, mesh=bgrid.mesh, in_specs=(bgrid.aux_spec,),
                out_specs=bgrid.aux_spec, check_vma=False,
            )(Cp)

        def step(s, Cm):
            return (shard_map(
                lambda Tb_l, Cml: batched_local(
                    Tb_l, Cml, cfg.lam, dt, cfg.spacing
                ),
                mesh=bgrid.mesh,
                in_specs=(bgrid.spec, bgrid.aux_spec),
                out_specs=bgrid.spec,
                check_vma=False,
            )(s[0], Cm),)

        return step, prepare

    def batched_step_fn(self, bgrid, variant: str = "shard",
                        donate: bool = False):
        """jitted steady-state `step(Tb, C) -> Tb` — one batched step as
        its own program (what the perf traffic gate audits: per-lane
        compiled bytes of the B-lane program vs B× the single-lane
        ideal, rocm_mpi_tpu/perf/traffic.py). For variants with a
        prepared coefficient (hide), C is the PREPARED operand —
        `batched_prepare_fn` builds it, exactly as prepared_step_fn
        splits the single-lane audit surface."""
        batched, _ = self._make_batched_step(bgrid, variant)

        def step(Tb, C):
            return batched((Tb,), C)[0]

        return jax.jit(step, donate_argnums=(0,) if donate else ())

    def batched_prepare_fn(self, bgrid, variant: str = "shard"):
        """jitted `prepare(Cp) -> C` for the batched variant's
        loop-invariant coefficient (identity for the prep-less
        variants) — the audit-surface companion of batched_step_fn."""
        _, prep = self._make_batched_step(bgrid, variant)
        if prep is None:
            return jax.jit(lambda C: C)
        return jax.jit(prep)

    def _ladder_step(self, bgrid):
        """The ladder's batched step, `step(state, (Cp, hold, dt_lam,
        inv_d2)) -> state`: `dt_lam` the per-lane dt·λ, multiplied in the
        compute dtype (batched_ladder_advance_fn)."""
        step = self.batched_ladder_step_fn(bgrid)
        return lambda s, P: (step(s[0], P[0], P[1], P[2], *P[3]),)

    def batched_ladder_step_fn(self, bgrid):
        """The UNJITTED per-step program of `batched_ladder_advance_fn` —
        `step(Tb, Cp, hold, dt_lam, *inv_d2) -> Tb`, the shard_map'd
        body the advance's loop repeats. Exposed separately so the
        traffic audit can price ONE ladder step (the HLO byte model
        reads the entry computation only; a loop body would be
        invisible to it).

        inv_d2 rides as ndim SEPARATE per-lane scalar operands, not one
        indexed (batch, ndim) vector: inside the fori_loop body XLA
        fuses the gathered-vector form differently from the standalone's
        folded constants and drifts a ulp — per-axis scalar operands
        compile to the identical multiplies
        (ops.diffusion.step_fused_padded_geom has the full story).
        """
        from rocm_mpi_tpu.ops.diffusion import step_fused_padded_geom
        from rocm_mpi_tpu.parallel.halo import exchange_halo_batched

        wire_mode = self.config.wire_mode
        ndim = bgrid.space.ndim

        def lane_local(Tb_l, Cl, Hb_l, dtlam_l, *invd2_l):
            Tp = exchange_halo_batched(Tb_l, bgrid, wire_mode=wire_mode)

            def lane(Tl, Tpl, Hl, a, *gs):
                new = step_fused_padded_geom(Tpl, Cl, a, gs)
                return jnp.where(Hl, Tl, new)

            return jax.vmap(lane)(Tb_l, Tp, Hb_l, dtlam_l, *invd2_l)

        return shard_map(
            lane_local,
            mesh=bgrid.mesh,
            in_specs=(bgrid.spec, bgrid.aux_spec, bgrid.spec,
                      bgrid.batch_spec)
            + (bgrid.batch_spec,) * ndim,
            out_specs=bgrid.spec,
            check_vma=False,
        )

    def batched_deep_advance_fn(
        self,
        batch: int | None = None,
        block_steps: int | None = None,
        bgrid=None,
        batch_dims: int = 1,
        devices=None,
        wire_mode: str | None = None,
    ):
        """(jitted `advance(Tb, Cp, n) -> Tb`, bgrid, k) — the deep-halo
        schedule against the space×batch mesh (make_deep_sweep with a
        BatchedGrid): one width-k exchange of the whole lane batch per k
        steps, the vmapped jnp local sweep. Uniform steps only (`n` a
        multiple of k for every lane — the bin scheduler routes
        heterogeneous-step bins to the per-step batched advance)."""
        from rocm_mpi_tpu.parallel.deep_halo import make_deep_sweep

        cfg = self.config
        bgrid = self._bgrid(batch, bgrid, batch_dims, devices)
        k = block_steps
        if k is None:
            from rocm_mpi_tpu.ops.pallas_kernels import _compute_itemsize

            k = default_deep_depth(
                bgrid.space.local_shape, _compute_itemsize(cfg.jax_dtype)
            )
        wm = cfg.wire_mode if wire_mode is None else wire_mode
        dt = cfg.jax_dtype(cfg.dt)
        sched = make_deep_sweep(bgrid, k, cfg.lam, dt, cfg.spacing,
                                wire_mode=wm)
        return self._sweep_advance(sched), bgrid, sched.k

    # ---- runs -----------------------------------------------------------

    def run(
        self, variant: str = "ap", nt: int | None = None,
        warmup: int | None = None, config: str | None = None,
    ) -> RunResult:
        """Run `nt` steps on the scan driver; time all but the first
        `warmup` (perf.jl:47-53; Driver.run). halo_transport='host' on
        variant 'shard' runs the host-staged numpy oracle instead."""
        cfg = self.config
        nt, warmup = self._windows(nt, warmup)
        if cfg.halo_transport == "host":
            if variant == "shard":
                return self._run_host_staged(nt, warmup)
            warn_host_transport_ignored(variant)
        if cfg.wire_mode != "f32" and variant in ("ap", "fused"):
            import warnings

            warnings.warn(
                f"wire_mode={cfg.wire_mode!r} is not honored by variant "
                f"{variant!r} — the GSPMD global-array variants have no "
                "explicit exchange to encode; use shard/perf/hide or the "
                "deep schedule.",
                stacklevel=2,
            )
        return super().run(variant, nt, warmup, config)

    def _run_single_shard(
        self, nt, warmup, multi_step_fn, granularity: int, granularity_kw: str,
        explicit: bool = False, extra_kw=None, program_cache=None,
    ) -> RunResult:
        """Shared scaffold of the single-shard fast paths: validate, pick a
        step granularity dividing both the warmup and timed windows (so one
        compiled program, built outside the timed window, serves both — the
        outer trip count stays dynamic), then tic/advance/toc.

        `multi_step_fn(T, Cp, lam, dt, spacing, n, <granularity_kw>=g)` is
        one of ops.pallas_kernels.fused_multi_step / fused_multi_step_hbm.
        `explicit` marks a caller-requested granularity: degradation (gcd
        against the windows, or the large-field chunk cap) then warns
        instead of staying silent.

        `program_cache` (a caller-held dict) keys the jitted advance by
        the full trace identity — physics config, granularity, kernel
        kwargs — so two runs of the SAME configuration reuse one
        compiled program instead of re-tracing per call (jax's jit cache
        keys on function identity, and each call here otherwise builds a
        fresh closure). bench.py's kernel-form ladder holds one dict
        across its rungs; the step counts stay out of the key on purpose
        (they ride the dynamic `n`).
        """
        cfg = self.config
        nt, warmup = self._windows(nt, warmup)
        if self.grid.nprocs != 1:
            raise ValueError("single-shard fast paths require an unsharded grid")
        key = granularity_kw
        gran = effective_block_steps(
            nt, warmup, granularity, warn=explicit, label=key, stacklevel=4
        )
        dt = cfg.jax_dtype(cfg.dt)

        kw = {key: gran}
        if key == "chunk":
            kw["warn_on_cap"] = explicit
        if extra_kw:
            kw.update(extra_kw)

        cache_key = None
        advance = None
        if program_cache is not None:
            cache_key = (
                getattr(multi_step_fn, "__qualname__", repr(multi_step_fn)),
                cfg.global_shape, cfg.lengths, cfg.dtype,
                cfg.lam, cfg.cp0,
                tuple(sorted(kw.items())),
            )
            advance = program_cache.get(cache_key)

        if advance is None:

            @functools.partial(jax.jit, donate_argnums=0)
            def advance(T, Cp, n):
                return multi_step_fn(T, Cp, cfg.lam, dt, cfg.spacing, n, **kw)

            if cache_key is not None:
                program_cache[cache_key] = advance

        return self._timed(advance, nt, warmup, variant=key)

    def run_vmem_resident(
        self,
        nt: int | None = None,
        warmup: int | None = None,
        chunk: int | None = None,
        body_form: str | None = None,
        pad_pow2: bool | None = None,
        config: str | None = None,
        program_cache: dict | None = None,
    ) -> RunResult:
        """Single-shard fast path: the whole nt-step loop inside one Pallas
        kernel, field VMEM-resident (ops.pallas_kernels.fused_multi_step).

        TPU-only optimization with no reference analog; only valid when the
        grid is unsharded (nprocs == 1) and fits the VMEM budget.

        `chunk` overrides the per-kernel step count (default
        DEFAULT_STEP_CHUNK): Mosaic compile time scales with the unroll, so
        a small chunk (e.g. 16) compiles in seconds where 256 takes tens —
        bench.py's floor measurement depends on this knob.

        `body_form`/`pad_pow2` select the kernel-form A/B candidates as
        trace-time kwargs (bench.py's stage-2.5 ladder); None keeps the
        module-constant hardware defaults. `config="auto"` fills any knob
        left None from the persistent tuning cache instead
        (tuning/resolve.py; a miss keeps the defaults, bitwise) — the
        resolution happens HERE, outside any trace, and the winners
        travel down as the same explicit kwargs. `program_cache` reuses
        compiled advances across same-config runs (_run_single_shard).
        """
        import rocm_mpi_tpu.ops.pallas_kernels as _pk
        from rocm_mpi_tpu.ops.pallas_kernels import (
            DEFAULT_STEP_CHUNK,
            fused_multi_step,
        )

        cfg = self.config
        if config == "auto":
            from rocm_mpi_tpu.tuning import resolve as tuning_resolve

            tuned = tuning_resolve.resolve(
                "diffusion.vmem_loop", cfg.global_shape, cfg.jax_dtype
            ) or {}
            if chunk is None and _pk.adoptable_vmem_chunk(
                tuned.get("chunk")
            ):
                chunk = tuned["chunk"]
                # Auto-resolved, not caller-requested: the gcd against
                # the windows below must not warn (explicit stays False).
                auto_chunk = True
            else:
                auto_chunk = False
            if body_form is None:
                body_form = tuned.get("body_form")
            if pad_pow2 is None:
                pad_pow2 = tuned.get("pad_pow2")
        elif config in (None, "default"):
            auto_chunk = False
        else:
            raise ValueError(
                f"config must be None, 'default' or 'auto', got {config!r}"
            )
        # Normalize the knobs to their effective values HERE (the same
        # resolution plan_vmem_loop would do at trace time): the
        # program-cache key must see "None" and the module default as
        # the identical trace they are, or bench's winner re-run would
        # re-trace the program its calibration rung already compiled.
        if body_form is None:
            body_form = _pk.EQC_BODY_FORM
        if pad_pow2 is None:
            pad_pow2 = _pk.VMEM_PAD_POW2
        return self._run_single_shard(
            nt,
            warmup,
            fused_multi_step,
            DEFAULT_STEP_CHUNK if chunk is None else chunk,
            "chunk",
            explicit=chunk is not None and not auto_chunk,
            extra_kw={"body_form": body_form, "pad_pow2": pad_pow2},
            program_cache=program_cache,
        )

    def run_hbm_blocked(
        self,
        nt: int | None = None,
        warmup: int | None = None,
        block_steps: int | None = None,
    ) -> RunResult:
        """Single-shard large-grid fast path: temporal blocking — every HBM
        sweep advances the field `block_steps` steps
        (ops.pallas_kernels.fused_multi_step_hbm), beating the 3-passes-per-
        step bound the reference's fused kernel is built around
        (perf.jl:55). Only valid when the grid is unsharded; the sharded
        variants keep per-step halo semantics.
        """
        from rocm_mpi_tpu.ops.pallas_kernels import (
            DEFAULT_TB_STEPS,
            fused_multi_step_hbm,
        )

        cfg = self.config
        k = DEFAULT_TB_STEPS if block_steps is None else block_steps
        nt_v = cfg.nt if nt is None else nt
        wu_v = cfg.warmup if warmup is None else warmup
        effective_block_steps(
            nt_v, wu_v, k, label="temporal blocking block_steps", stacklevel=2
        )
        return self._run_single_shard(
            nt, warmup, fused_multi_step_hbm, k, "block_steps"
        )

    def effective_deep_depth(
        self,
        nt: int | None = None,
        warmup: int | None = None,
        block_steps: int | None = None,
        warn: bool = True,
        config: str | None = None,
    ) -> int:
        """The sweep depth run_deep will actually execute for these
        arguments — THE source of truth for callers labeling artifacts by
        depth (apps/_common.py), so label and executed k cannot drift.
        Policy: defaults route through default_deep_depth (VMEM-aware,
        shard-clamped) — unless `config="auto"` finds a tuned depth for
        this shard/topology in the tuning cache
        (parallel.deep_halo.resolve_deep_k; note a different k is a
        different sweep SCHEDULE, fp-reordered vs the default depth, not
        a bitwise-neutral knob like the kernel forms); explicit depths
        keep make_deep_sweep's strict shard-extent validation; any of
        the three is then gcd'd against both timing windows.
        """
        cfg = self.config
        if block_steps is None:
            from rocm_mpi_tpu.ops.pallas_kernels import _compute_itemsize
            from rocm_mpi_tpu.parallel.deep_halo import resolve_deep_k

            k = resolve_deep_k(self.grid, cfg.jax_dtype, config)
            if k is None:
                # bf16 is storage-only in the local kernels (f32
                # in-kernel): size the depth at the compute width.
                k = default_deep_depth(
                    self.grid.local_shape, _compute_itemsize(cfg.jax_dtype)
                )
        else:
            k = block_steps
        return effective_block_steps(
            cfg.nt if nt is None else nt,
            cfg.warmup if warmup is None else warmup,
            k,
            label="deep-halo sweep depth",
            warn=warn,
            stacklevel=3,
        )

    def effective_wire_mode(
        self, wire_mode: str | None = None, config: str | None = None,
    ) -> str:
        """The state exchange's on-wire precision a deep run will use:
        an explicit `wire_mode` wins, else `config="auto"` consults the
        tuning cache (the PR-12 wire axis of the "diffusion.deep"
        entry), else the config's wire_mode field (default "f32")."""
        if wire_mode is not None:
            return wire_mode
        from rocm_mpi_tpu.parallel.deep_halo import resolve_deep_config

        tuned = resolve_deep_config(
            self.grid, self.config.jax_dtype, config
        )["wire_mode"]
        return tuned if tuned is not None else self.config.wire_mode

    def _deep_schedule(self, k: int, wire_mode: str):
        """The deep-halo schedule (parallel.deep_halo.make_deep_sweep)
        behind deep_advance_fn and run_deep. Works on any mesh (including
        1 device, where it reduces to the VMEM-resident loop plus crop
        overhead). f32/bf16 only on real TPUs (the local kernel is
        Pallas). Default depth 32 — the measured single-chip optimum at
        252² with the A/c kernel (r3: k=8 1.02 µs/step, k=16 0.889, k=32
        0.848); on a pod slice larger k also divides the message count
        further. Mid-size shards prefer the deepest VMEM-fitting depth;
        HBM-resident shards cap the default at 8 (default_deep_depth)."""
        from rocm_mpi_tpu.parallel.deep_halo import make_deep_sweep

        cfg = self.config
        if cfg.halo_transport == "host":
            # The warning lives with the schedule builder so EVERY deep
            # caller (run_deep, the --checkpoint segmented loop) gets it.
            warn_host_transport_ignored("deep", stacklevel=4)
        return make_deep_sweep(self.grid, k, cfg.lam, cfg.jax_dtype(cfg.dt),
                               cfg.spacing, wire_mode=wire_mode)

    def _run_host_staged(self, nt: int, warmup: int) -> RunResult:
        """Debug oracle: numpy stepper with host-staged halos
        (IGG_ROCMAWARE_MPI=0 analog; parallel.halo.HostStagedStepper)."""
        import numpy as np

        from rocm_mpi_tpu.parallel.halo import HostStagedStepper

        if jax.process_count() > 1:
            raise NotImplementedError(
                "halo_transport='host' is a single-process debug oracle; it "
                "needs every shard host-addressable. Run it on one host "
                "(virtual devices) to bisect transport vs math."
            )
        cfg = self.config
        T, Cp = self.init_state()
        T_np, Cp_np = np.asarray(T), np.asarray(Cp)
        stepper = HostStagedStepper(self.grid, cfg.lam, cfg.dt,
                                    wire_mode=cfg.wire_mode)
        timer = metrics.Timer(label="step_window", phase="step",
                              steps=nt - warmup, variant="shard-host",
                              workload="diffusion")
        T_np = stepper.run(T_np, Cp_np, warmup)
        with timer:
            T_np = stepper.run(T_np, Cp_np, nt - warmup)
        wtime = timer.elapsed
        T_out = jax.device_put(T_np, self.grid.sharding)
        return RunResult(T=T_out, wtime=wtime, nt=nt, warmup=warmup, config=cfg)

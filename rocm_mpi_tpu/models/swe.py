"""Linearized shallow-water equations — the framework's third workload.

Purpose: where the wave model shows the framework layers are
workload-agnostic for a *state pair with one exchanged field*, this model
exercises the genuinely coupled case — ndim+1 fields (surface height h and
one face velocity per axis) whose updates read neighbors of *different*
fields — on the same mesh/halo/Pallas/schedule machinery. This is the
shape of real multi-field stencil codes (ocean/atmosphere dynamical cores,
staggered-grid electromagnetics), and it is what drove the pytree-state
generalization of parallel.overlap.make_overlap_step (r4). No reference
analog (the reference ships exactly one physics model): additive, not
parity.

Physics and scheme: see ops/swe_kernels.py — forward-backward
(symplectic-Euler) time stepping of the C-grid-staggered linear system

    h' = h − dt·H·∇⁻·u,    u_a' = M_a ∘ (u_a − dt·g·∂a⁺ h')

in a closed basin (wall faces masked to exactly 0.0 — mask-as-data). Two
machine-checkable invariants the other workloads cannot offer together:

  * EXACT mass conservation — the closed-basin divergence telescopes to
    wall−wall = 0, so sum(h) is constant to fp rounding;
  * algebraic time-reversibility — the update has the closed-form inverse
    u = u' + dt·g·M∘∂⁺h';  h = h' + dt·H·∇⁻·u  (inverse sub-steps in
    reverse order), so a trajectory can be run back to its IC.

Variants mirror the flagship's ladder:
  "ap"   — global-array jnp rolls (GSPMD partitions; wraparound reads the
           opposite wall face, which the masks hold at 0 — exact).
  "perf" — shard_map + one exchange of the full state + the whole-block
           Pallas padded kernel (ops.swe_kernels.swe_step_padded_pallas);
           on one device, the row-striped kernel on the unpadded state
           with no exchange (ops.swe_kernels.masked_swe_step).
  "hide" — the boundary-slab/interior overlap decomposition, pytree
           state through parallel.overlap: in 2D the striped kernel
           over the whole shard as the interior and the slab kernel
           (ops.swe_kernels.swe_slab_step) on the slabs; in 3D perf's
           padded kernel on every region.
Plus run_deep (width-k ghost exchange of all fields once per k steps) and
run_vmem_resident (whole loop in one Pallas kernel, single shard).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from rocm_mpi_tpu.utils.compat import shard_map

from rocm_mpi_tpu.config import DTYPES
from rocm_mpi_tpu.models.driver import Driver, Result, effective_block_steps
from rocm_mpi_tpu.ops.diffusion import gaussian_ic
from rocm_mpi_tpu.ops.swe_kernels import rolled_swe_step, swe_coeffs
from rocm_mpi_tpu.parallel.halo import exchange_halo


@dataclasses.dataclass(frozen=True)
class SWEConfig:
    """Knobs of a shallow-water run (same shape-vocabulary as
    DiffusionConfig/WaveConfig)."""

    global_shape: tuple[int, ...] = (128, 128)
    lengths: tuple[float, ...] = (10.0, 10.0)
    H0: float = 1.0  # resting depth
    g: float = 1.0  # gravity
    cfl: float = 0.5  # Courant number vs c = √(g·H0), < 1
    nt: int = 1000
    warmup: int = 10
    dtype: str = "f64"
    dims: tuple[int, ...] | None = None
    b_width: tuple[int, ...] = (32, 4)
    # On-wire halo slab precision (parallel/wire.py; same contract as
    # DiffusionConfig.wire_mode — stateful modes are deep-only).
    wire_mode: str = "f32"

    def __post_init__(self):
        if len(self.lengths) != len(self.global_shape):
            raise ValueError("lengths rank must match global_shape rank")
        if self.dtype not in DTYPES:
            raise ValueError(f"dtype must be one of {sorted(DTYPES)}")
        from rocm_mpi_tpu.parallel import wire

        wire.validate_mode(self.wire_mode)

    @property
    def ndim(self) -> int:
        return len(self.global_shape)

    @property
    def jax_dtype(self):
        return DTYPES[self.dtype]

    @property
    def spacing(self) -> tuple[float, ...]:
        return tuple(l / n for l, n in zip(self.lengths, self.global_shape))

    @property
    def wave_speed(self) -> float:
        return math.sqrt(self.g * self.H0)

    @property
    def dt(self) -> float:
        """CFL-stable forward-backward step: cfl·min(d)/(c·√ndim)."""
        return (
            self.cfl
            * min(self.spacing)
            / (self.wave_speed * math.sqrt(self.ndim))
        )


@dataclasses.dataclass
class SWERunResult(Result):
    h: jax.Array
    us: tuple

    @property
    def field(self):
        return self.h

    @property
    def n_passes(self) -> int:
        # 2·(ndim+1) whole-array passes per step: read + write each of the
        # h and u_a state fields (masks are coefficient traffic, excluded —
        # the same accounting stance as the diffusion T_eff formula).
        return 2 * (len(self.us) + 1)


class ShallowWater(Driver):
    """Forward-backward linear shallow water on a sharded global grid."""

    workload = "swe"
    n_state = 2

    def face_masks(self):
        """Per-axis face masks as data arrays: exactly 0.0 on the global
        high wall face (index n_a−1 along axis a), 1.0 elsewhere. The low
        wall is the zero-ghost convention (parallel.halo). Sharded like
        the state so every schedule slices them locally."""
        cfg, grid = self.config, self.grid
        dtype = cfg.jax_dtype

        @functools.partial(
            jax.jit, static_argnums=0, out_shardings=grid.sharding
        )
        def make(axis):
            gidx = lax.broadcasted_iota(
                jnp.int32, grid.global_shape, axis
            )
            return jnp.where(
                gidx >= grid.global_shape[axis] - 1,
                jnp.zeros(grid.global_shape, dtype),
                jnp.ones(grid.global_shape, dtype),
            )

        return tuple(make(a) for a in range(cfg.ndim))

    def init_state(self):
        """(h, us): Gaussian surface bump at rest (all velocities zero —
        wall faces therefore start, and the masks keep them, at 0)."""
        cfg, grid = self.config, self.grid
        dtype = cfg.jax_dtype

        @functools.partial(jax.jit, out_shardings=grid.sharding)
        def make_h():
            return gaussian_ic(
                grid.coord_mesh(dtype=dtype), cfg.lengths, dtype=dtype
            )

        @functools.partial(jax.jit, out_shardings=grid.sharding)
        def make_u():
            return jnp.zeros(grid.global_shape, dtype)

        return make_h(), tuple(make_u() for _ in range(cfg.ndim))

    def state_and_aux(self):
        return self.init_state(), self.face_masks()

    def _result(self, state, **window) -> SWERunResult:
        return SWERunResult(h=state[0], us=state[1], **window)

    def _step(self, variant: str):
        """(`step((h, us), P) -> (h', us')`, prepare): `P` is the face
        masks `Mus` as they are (`prepare` None), except on one device's
        `perf` (and `hide`, which routes there) and the 2D sharded
        `hide`, where `prepare` packs them once a call into the 1-byte
        wall code (ops.swe_kernels.pack_walls) that the kernels read.
        Those paths honour any 0/1 mask pattern, an interior wall or all
        ones included."""
        cfg, grid = self.config, self.grid
        dt = cfg.dt
        cH, cg = swe_coeffs(dt, cfg.spacing, cfg.H0, cfg.g)

        if variant == "ap":

            def step(s, Mus):
                return rolled_swe_step(*s, Mus, cH, cg)

            return step, None
        if variant == "shard":
            # The explicit-decomposition jnp rung (the diffusion/wave
            # "shard" vocabulary): one exchange of the full state + the
            # pure-jnp padded forward-backward update, walls as mask
            # data. Pallas-free — the per-lane body the batched
            # multi-tenant advance vmaps (docs/SERVING.md).
            from rocm_mpi_tpu.ops.swe_kernels import swe_step_padded

            def step(s, Mus):
                h, us = s

                def local(hl, *rest):
                    uls, Ml = rest[: cfg.ndim], rest[cfg.ndim:]
                    Sp = tuple(
                        exchange_halo(f, grid, wire_mode=cfg.wire_mode)
                        for f in (hl,) + tuple(uls)
                    )
                    return swe_step_padded(
                        Sp, Ml, (cfg.H0, cfg.g), dt, cfg.spacing
                    )

                outs = shard_map(
                    local,
                    mesh=grid.mesh,
                    in_specs=(grid.spec,) * (2 * cfg.ndim + 1),
                    out_specs=(grid.spec,) * (cfg.ndim + 1),
                    check_vma=False,
                )(h, *us, *Mus)
                return outs[0], tuple(outs[1:])

            return step, None
        if variant == "perf":
            from rocm_mpi_tpu.ops.swe_kernels import (
                masked_swe_step,
                pack_walls,
                swe_step_padded_pallas,
            )

            if grid.nprocs == 1:
                # Unsharded: no neighbours, the block edge IS the global
                # wall — one striped kernel a step, no exchange, no pad,
                # the walls read as the code packed once a call.
                def step(s, walls):
                    return masked_swe_step(*s, walls, cH, cg)

                return step, pack_walls

            def step(s, Mus):
                h, us = s

                def local(hl, *rest):
                    uls, Ml = rest[: cfg.ndim], rest[cfg.ndim:]
                    Sp = tuple(
                        exchange_halo(f, grid, wire_mode=cfg.wire_mode)
                        for f in (hl,) + tuple(uls)
                    )
                    outs = swe_step_padded_pallas(
                        Sp, Ml, (cfg.H0, cfg.g), dt, cfg.spacing
                    )
                    return outs

                outs = shard_map(
                    local,
                    mesh=grid.mesh,
                    in_specs=(grid.spec,) * (2 * cfg.ndim + 1),
                    out_specs=(grid.spec,) * (cfg.ndim + 1),
                    check_vma=False,
                )(h, *us, *Mus)
                return outs[0], tuple(outs[1:])

            return step, None
        if variant == "hide":
            from rocm_mpi_tpu.ops.swe_kernels import swe_step_padded_pallas
            from rocm_mpi_tpu.parallel.overlap import make_overlap_step

            if grid.nprocs == 1:
                # No neighbors → nothing to hide (same routing policy as
                # the diffusion and wave models' single-device hide).
                return self._step("perf")
            if cfg.ndim == 2:
                return self._hide_2d(cH, cg)

            def pu(Sp, Ml, lam, dt_, spacing):
                del lam
                return swe_step_padded_pallas(
                    Sp, Ml, (cfg.H0, cfg.g), dt_, spacing
                )

            # Walls ride the mask data — no Dirichlet where (the Cm-style
            # mask_boundary=False contract).
            local = make_overlap_step(
                grid, pu, cfg.b_width, mask_boundary=False,
                wire_mode=cfg.wire_mode,
            )

            def step(s, Mus):
                h, us = s

                def shard_fn(hl, *rest):
                    uls, Ml = rest[: cfg.ndim], rest[cfg.ndim:]
                    return local(
                        (hl,) + tuple(uls), tuple(Ml), None, dt,
                        cfg.spacing,
                    )

                outs = shard_map(
                    shard_fn,
                    mesh=grid.mesh,
                    in_specs=(grid.spec,) * (2 * cfg.ndim + 1),
                    out_specs=(grid.spec,) * (cfg.ndim + 1),
                    check_vma=False,
                )(h, *us, *Mus)
                return outs[0], tuple(outs[1:])

            return step, None
        raise ValueError(
            f"unknown SWE variant {variant!r} (ap, shard, perf, hide)"
        )

    def _hide_2d(self, cH, cg):
        """The 2D sharded `hide` step and its `prepare`, pack_walls: the
        overlap splice (parallel.overlap) with `masked_swe_step` over the
        whole unpadded shard as its interior (its values next to a shard
        face that is not a global face are wrong, and the slabs overwrite
        them) and `swe_slab_step` on each boundary slab's window, both
        reading the shard's wall code, packed once a call."""
        from rocm_mpi_tpu.ops.swe_kernels import (
            masked_swe_step,
            pack_walls,
            swe_slab_step,
        )
        from rocm_mpi_tpu.parallel.overlap import make_overlap_step

        cfg, grid = self.config, self.grid

        def interior(S, walls, lam, dt_, spacing):
            h, us = masked_swe_step(S[0], S[1:], walls, cH, cg)
            return (h, *us)

        def slab(Sp, walls, lam, dt_, spacing):
            return swe_slab_step(Sp, walls, cH, cg)

        local = make_overlap_step(
            grid, slab, cfg.b_width, mask_boundary=False,
            wire_mode=cfg.wire_mode, interior_update=interior,
        )

        def step(s, walls):
            h, us = s
            outs = shard_map(
                lambda hl, ul, vl, wl: local((hl, ul, vl), wl, None,
                                             cfg.dt, cfg.spacing),
                mesh=grid.mesh,
                in_specs=(grid.spec,) * 4,
                out_specs=(grid.spec,) * 3,
                check_vma=False,
            )(h, *us, walls)
            return outs[0], tuple(outs[1:])

        return step, pack_walls

    # ---- multi-tenant batching (docs/SERVING.md) ------------------------

    def _make_batched_step(self, bgrid, variant: str):
        """(`step((hb, usb), Mus) -> (hb', usb')`, prepare-or-None) over
        lane-batched SWE state; the face masks `Mus` are UNBATCHED
        (wall geometry is config-derived, shared by every lane). Same
        vocabulary (and return convention) as
        HeatDiffusion._make_batched_step."""
        from rocm_mpi_tpu.parallel.halo import exchange_halo_batched

        cfg = self.config
        ndim = cfg.ndim
        dt = cfg.dt
        cH, cg = swe_coeffs(dt, cfg.spacing, cfg.H0, cfg.g)

        if variant == "ap":

            def step(s, Mus):
                return jax.vmap(
                    lambda h, us: rolled_swe_step(h, us, Mus, cH, cg),
                    in_axes=(0, 0),
                )(*s)

            return step, None

        if variant != "shard":
            raise ValueError(
                f"batched SWE advance supports variants 'shard', 'ap'; "
                f"got {variant!r} (the Pallas/overlap rungs are "
                "single-lane)"
            )

        from rocm_mpi_tpu.ops.swe_kernels import swe_step_padded

        def lane_local(hb_l, *rest):
            ub_ls, Ml = rest[:ndim], rest[ndim:]
            padded = tuple(
                exchange_halo_batched(f, bgrid, wire_mode=cfg.wire_mode)
                for f in (hb_l,) + tuple(ub_ls)
            )

            def lane(*Sp):
                return swe_step_padded(
                    Sp, Ml, (cfg.H0, cfg.g), dt, cfg.spacing
                )

            return jax.vmap(lane)(*padded)

        def step(s, Mus):
            hb, usb = s
            outs = shard_map(
                lane_local,
                mesh=bgrid.mesh,
                in_specs=(bgrid.spec,) * (ndim + 1)
                + (bgrid.aux_spec,) * ndim,
                out_specs=(bgrid.spec,) * (ndim + 1),
                check_vma=False,
            )(hb, *usb, *Mus)
            return outs[0], tuple(outs[1:])

        return step, None

    def run_vmem_resident(
        self, nt: int | None = None, warmup: int | None = None,
        chunk: int | None = None, config: str | None = None,
    ) -> SWERunResult:
        """Single-shard fast path: the whole coupled loop inside one
        Pallas kernel, all ndim+1 fields VMEM-resident
        (ops.swe_kernels.swe_multi_step). `config="auto"` fills an unset
        chunk from the tuning cache (op "swe.vmem_loop"), resolved here
        outside any trace and gcd'd against the windows."""
        from rocm_mpi_tpu.ops.pallas_kernels import DEFAULT_STEP_CHUNK
        from rocm_mpi_tpu.ops.swe_kernels import swe_multi_step

        cfg = self.config
        if self.grid.nprocs != 1:
            raise ValueError(
                "the VMEM-resident path requires an unsharded grid"
            )
        explicit = chunk is not None
        if config == "auto" and chunk is None:
            from rocm_mpi_tpu.ops.pallas_kernels import adoptable_vmem_chunk
            from rocm_mpi_tpu.tuning import resolve as tuning_resolve

            tuned = tuning_resolve.resolve(
                "swe.vmem_loop", cfg.global_shape, cfg.jax_dtype
            )
            if tuned and adoptable_vmem_chunk(tuned.get("chunk")):
                chunk = tuned["chunk"]
        elif config not in (None, "default", "auto"):
            raise ValueError(
                f"config must be None, 'default' or 'auto', got {config!r}"
            )
        # warn=explicit: a caller-requested chunk degrades loudly (the
        # wave/diffusion editions' policy); framework-plumbed and
        # auto-resolved preferences degrade silently.
        eff_chunk = effective_block_steps(
            cfg.nt if nt is None else nt,
            cfg.warmup if warmup is None else warmup,
            DEFAULT_STEP_CHUNK if chunk is None else chunk,
            warn=explicit, label="SWE VMEM chunk",
        )

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def advance(h, us, Mus, n):
            return swe_multi_step(
                h, us, Mus, cfg.dt, cfg.spacing, cfg.H0, cfg.g, n,
                chunk=eff_chunk, warn_on_cap=False,
            )

        return self._timed(advance, nt, warmup, variant="vmem")

    DEFAULT_DEEP_STEPS = 8

    def effective_deep_depth(
        self,
        nt: int | None = None,
        warmup: int | None = None,
        block_steps: int | None = None,
        warn: bool = True,
    ) -> int:
        """The sweep depth run_deep will actually execute — the labeling
        source of truth (same policy as the diffusion and wave models,
        ADVICE r3: a DEFAULT depth clamps to the shard, an EXPLICIT one is
        gcd-degraded against the windows and then raises if it still
        exceeds the shard)."""
        cfg = self.config
        explicit = block_steps is not None
        if block_steps is None:
            block_steps = min(
                self.DEFAULT_DEEP_STEPS, min(self.grid.local_shape)
            )
        eff = effective_block_steps(
            cfg.nt if nt is None else nt,
            cfg.warmup if warmup is None else warmup,
            block_steps,
            label="SWE deep-halo sweep depth",
            warn=warn,
            stacklevel=3,
        )
        if explicit and eff > min(self.grid.local_shape):
            raise ValueError(
                f"SWE deep-halo sweep depth {eff} exceeds a local shard "
                f"extent {self.grid.local_shape}; ghost slices need "
                "width <= shard"
            )
        return eff

    def _deep_schedule(self, k: int, wire_mode: str):
        """The SWE deep schedule (parallel.deep_halo.make_swe_deep_sweep):
        ONE width-k ghost exchange of the whole coupled state per k
        steps. Its `prepare` builds the padded face masks from the
        state's dtype and sharding (`_deep_operand`), not from `Mus`."""
        from rocm_mpi_tpu.parallel.deep_halo import make_swe_deep_sweep

        cfg = self.config
        return make_swe_deep_sweep(self.grid, k, cfg.dt, cfg.spacing,
                                   cfg.H0, cfg.g, wire_mode=wire_mode)

    def _deep_operand(self, state, aux):
        return state[0]

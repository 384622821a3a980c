"""Acoustic wave equation — the framework's second workload.

Purpose: demonstrate that the framework layers the diffusion flagship is
built from — cartesian mesh (parallel.mesh), ppermute halo exchange
(parallel.halo), Pallas padded-block kernels (ops.*), block_until_ready-synced timers
(utils.metrics) — are workload-agnostic. This is what a *user* adding their
own stencil model to the framework writes; the reference has no analog (it
ships exactly one physics model), so this module is additive, not parity.

Physics: u_tt = c² ∇²u with Dirichlet boundaries (edge cells held at their
initial values — the same boundary design as the diffusion model, reusing
the zero-ghost halo convention). Leapfrog (central-difference) time
stepping over the state pair (U, U_prev):

    U⁺ = 2U − U⁻ + dt²·c²·∇²U

which is second-order accurate and exactly time-reversible — the
reversibility test in tests/test_wave.py runs the trajectory backward to
its initial state at rounding-level tolerance, a correctness check the
dissipative diffusion model cannot offer.

Variants mirror the flagship's ladder where it transfers:
  "ap"   — global-array jnp ops; GSPMD partitions and inserts comms.
  "perf" — shard_map + exchange_halo + whole-block Pallas kernel
           (ops.wave_kernels), explicit Dirichlet mask.
  "hide" — the masked-contract kernel (ops.wave_kernels
           .wave_step_padded_masked_pallas) on the boundary-slab/interior
           overlap decomposition (parallel.overlap): the U exchange is
           dataflow-independent of the interior update, so XLA may hide
           it — the second workload on the reference's intended variant
           (3) schedule (hide.jl:94-101). The Dirichlet hold rides the
           prepared (M, Cw) data operands (a branch-free select,
           fp-identical to perf on updating cells), so no trailing
           whole-shard `where` and no per-step mask rebuild.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from rocm_mpi_tpu.utils.compat import shard_map

from rocm_mpi_tpu.config import DTYPES
from rocm_mpi_tpu.models.driver import Driver, Result, effective_block_steps
from rocm_mpi_tpu.ops.diffusion import gaussian_ic
from rocm_mpi_tpu.ops.stencil import inn
from rocm_mpi_tpu.ops.wave_kernels import wave_step_padded  # noqa: F401  (re-export)
from rocm_mpi_tpu.parallel.halo import exchange_halo, global_boundary_mask


@dataclasses.dataclass(frozen=True)
class WaveConfig:
    """Knobs of a wave run (same shape-vocabulary as DiffusionConfig)."""

    global_shape: tuple[int, ...] = (128, 128)
    lengths: tuple[float, ...] = (10.0, 10.0)
    c0: float = 1.0  # wave speed
    cfl: float = 0.5  # Courant number, < 1 (dt already has the 1/√ndim factor)
    nt: int = 1000
    warmup: int = 10
    dtype: str = "f64"
    dims: tuple[int, ...] | None = None
    # Boundary-frame width of the hide variant (the reference's b_width
    # knob, hide.jl:42 — same default as DiffusionConfig; clamped per-shard
    # by parallel.overlap.effective_b_width).
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
    def dt(self) -> float:
        """CFL-stable leapfrog step: cfl·min(d)/(c0·√ndim)."""
        return (
            self.cfl * min(self.spacing) / (self.c0 * math.sqrt(self.ndim))
        )


def wave_step_fused(U, Uprev, C2, dt, spacing):
    """Global-array leapfrog step; edge cells pass through unchanged."""
    core = tuple(slice(1, -1) for _ in range(U.ndim))
    return U.at[core].set(
        wave_step_padded(U, inn(Uprev), inn(C2), dt, spacing)
    )


@dataclasses.dataclass
class WaveRunResult(Result):
    U: jax.Array
    # 4 whole-array passes per step: read U, U_prev, C2; write U⁺.
    n_passes = 4

    @property
    def field(self):
        return self.U


class AcousticWave(Driver):
    """Leapfrog acoustic wave on a sharded global grid."""

    workload = "wave"
    n_state = 2

    def init_state(self):
        """(U, U_prev, C2): Gaussian displacement at rest, uniform c²."""
        cfg, grid = self.config, self.grid
        dtype = cfg.jax_dtype

        @functools.partial(jax.jit, out_shardings=grid.sharding)
        def make_U():
            return gaussian_ic(
                grid.coord_mesh(dtype=dtype), cfg.lengths, dtype=dtype
            )

        @functools.partial(jax.jit, out_shardings=grid.sharding)
        def make_C2():
            return jnp.full(
                grid.global_shape, cfg.c0 * cfg.c0, dtype=dtype
            )

        U = make_U()
        return U, jnp.copy(U), make_C2()

    def state_and_aux(self):
        U, Uprev, C2 = self.init_state()
        return (U, Uprev), C2

    def _result(self, state, **window) -> WaveRunResult:
        return WaveRunResult(U=state[0], **window)

    def _mask_prepare(self):
        """prepare(C2) -> (M, Cw): the interior mask (1.0 on updating
        cells, exactly 0.0 on the global Dirichlet edge) and the masked
        coefficient Cw = dt²·c²·M — the wave edition of the diffusion Cm
        contract, computed ONCE per jitted program from global-array ops
        (GSPMD shards them like the state). The leapfrog needs M itself
        because a zeroed coefficient alone gives 2U − U⁻ ≠ U
        (ops/wave_kernels.py module docstring)."""
        cfg, grid = self.config, self.grid
        dt = cfg.jax_dtype(cfg.dt)
        dt2 = dt * dt

        def prepare(C2):
            from rocm_mpi_tpu.ops.wave_kernels import interior_mask

            M = interior_mask(grid.global_shape, C2.dtype)
            return M, dt2 * C2 * M

        return prepare

    def _step(self, variant: str):
        """(step, prepare): `step((U, Uprev), P) -> (U⁺, U)` with `P` the
        loop-invariant operands `prepare(C2)` builds once per jitted
        program — C2 itself for the variants with no prepare."""
        cfg, grid = self.config, self.grid
        dt = cfg.jax_dtype(cfg.dt)

        if variant == "ap":

            def step(s, C2):
                U, Uprev = s
                return wave_step_fused(U, Uprev, C2, dt, cfg.spacing), U

            return step, None
        if variant == "shard":
            # The explicit-decomposition jnp rung (the diffusion model's
            # "shard" vocabulary): exchange_halo + the pure-jnp padded
            # leapfrog update + Dirichlet mask. Pallas-free by
            # construction — the f64-safe explicit path on TPU, and the
            # per-lane body the batched multi-tenant advance vmaps
            # (docs/SERVING.md: batched results must be bitwise-equal to
            # a standalone run of the SAME op sequence).
            def step(s, C2):
                U, Uprev = s

                def local(Ul, Upl, C2l):
                    pad = exchange_halo(Ul, grid, wire_mode=cfg.wire_mode)
                    new = wave_step_padded(pad, Upl, C2l, dt, cfg.spacing)
                    return jnp.where(global_boundary_mask(grid), Ul, new)

                new = shard_map(
                    local,
                    mesh=grid.mesh,
                    in_specs=(grid.spec,) * 3,
                    out_specs=grid.spec,
                    check_vma=False,
                )(U, Uprev, C2)
                return new, U

            return step, None
        if variant == "perf":
            from rocm_mpi_tpu.ops.wave_kernels import wave_step_padded_pallas

            def step(s, C2):
                U, Uprev = s

                def local(Ul, Upl, C2l):
                    pad = exchange_halo(Ul, grid,
                                        wire_mode=cfg.wire_mode)
                    new = wave_step_padded_pallas(
                        pad, Upl, C2l, dt, cfg.spacing
                    )
                    return jnp.where(global_boundary_mask(grid), Ul, new)

                new = shard_map(
                    local,
                    mesh=grid.mesh,
                    in_specs=(grid.spec,) * 3,
                    out_specs=grid.spec,
                    check_vma=False,
                )(U, Uprev, C2)
                return new, U

            return step, None
        if variant == "hide":
            # Comm/compute overlap for the leapfrog (VERDICT r3 #5): the
            # same boundary-slab/interior decomposition as the diffusion
            # flagship's hide rung (parallel.overlap, the reference's
            # intended variant (3) semantics, hide.jl:94-101) — only U is
            # exchanged; (U_prev, M, Cw) ride along as core-only aux
            # operands. Mask-as-data contract: the Dirichlet hold is a
            # branch-free select inside the region kernel (bitwise-
            # identical to perf's expression on updating cells), so no
            # trailing whole-shard `where` and no per-step mask rebuild.
            from rocm_mpi_tpu.ops.wave_kernels import (
                wave_step_padded_masked_pallas,
            )
            from rocm_mpi_tpu.parallel.overlap import make_overlap_step

            if grid.nprocs == 1:
                # No neighbors → nothing to hide; strip bookkeeping is pure
                # overhead. Route to perf (same policy as the diffusion
                # model's single-device hide).
                return self._step("perf")

            def pu(tp, aux, lam, dt_, spacing):
                del lam, dt_
                return wave_step_padded_masked_pallas(
                    tp, aux[0], aux[1], aux[2], spacing
                )

            local = make_overlap_step(
                grid, pu, cfg.b_width, mask_boundary=False,
                wire_mode=cfg.wire_mode,
            )

            def step(s, P):
                U, Uprev = s
                M, Cw = P
                new = shard_map(
                    lambda Ul, Upl, Ml, Cwl: local(
                        Ul, (Upl, Ml, Cwl), None, dt, cfg.spacing
                    ),
                    mesh=grid.mesh,
                    in_specs=(grid.spec,) * 4,
                    out_specs=grid.spec,
                    check_vma=False,
                )(U, Uprev, M, Cw)
                return new, U

            return step, self._mask_prepare()
        raise ValueError(
            f"unknown wave variant {variant!r} (ap, shard, perf, hide)"
        )

    # ---- multi-tenant batching (docs/SERVING.md) ------------------------

    def _make_batched_step(self, bgrid, variant: str):
        """(`step((Ub, Upb), C2) -> (Ub⁺, Ub)`, prepare-or-None) over
        lane-batched leapfrog state; `C2` is the UNBATCHED squared wave
        speed every lane shares. Same vocabulary (and return
        convention) as HeatDiffusion._make_batched_step."""
        from rocm_mpi_tpu.parallel.halo import exchange_halo_batched

        cfg = self.config
        space = bgrid.space
        dt = cfg.jax_dtype(cfg.dt)

        if variant == "ap":

            def step(s, C2):
                Ub, Upb = s
                new = jax.vmap(
                    lambda U, Up: wave_step_fused(U, Up, C2, dt,
                                                  cfg.spacing)
                )(Ub, Upb)
                return new, Ub

            return step, None

        if variant != "shard":
            raise ValueError(
                f"batched wave advance supports variants 'shard', 'ap'; "
                f"got {variant!r} (the Pallas/overlap rungs are "
                "single-lane)"
            )

        def lane_local(Ub_l, Upb_l, C2l):
            pad = exchange_halo_batched(Ub_l, bgrid,
                                        wire_mode=cfg.wire_mode)
            mask = global_boundary_mask(space)

            def lane(Ul, Upl, padl):
                new = wave_step_padded(padl, Upl, C2l, dt, cfg.spacing)
                return jnp.where(mask, Ul, new)

            return jax.vmap(lane)(Ub_l, Upb_l, pad)

        def step(s, C2):
            Ub, Upb = s
            new = shard_map(
                lane_local,
                mesh=bgrid.mesh,
                in_specs=(bgrid.spec, bgrid.spec, bgrid.aux_spec),
                out_specs=bgrid.spec,
                check_vma=False,
            )(Ub, Upb, C2)
            return new, Ub

        return step, None

    def _ladder_step(self, bgrid):
        """The ladder's batched step, `step(state, (C2, hold, dt2,
        inv_d2)) -> (U⁺, U)`: `dt2` the per-lane dt², and `inv_d2`
        precomputed host-side in f64 from each lane's ORIGINAL-shape
        config (ops.wave_kernels.wave_step_padded_geom; per-axis scalars,
        not an indexed vector — the diffusion edition's ulp note applies
        here too; batched_ladder_advance_fn)."""
        from rocm_mpi_tpu.ops.wave_kernels import wave_step_padded_geom
        from rocm_mpi_tpu.parallel.halo import exchange_halo_batched

        cfg = self.config
        ndim = bgrid.space.ndim

        def lane_local(Ub_l, Upb_l, C2l, Hb_l, dt2_l, *invd2_l):
            pad = exchange_halo_batched(Ub_l, bgrid,
                                        wire_mode=cfg.wire_mode)

            def lane(Ul, Upl, padl, Hl, a, *gs):
                new = wave_step_padded_geom(padl, Upl, C2l, a, gs)
                return jnp.where(Hl, Ul, new)

            return jax.vmap(lane)(Ub_l, Upb_l, pad, Hb_l, dt2_l,
                                  *invd2_l)

        inner = shard_map(
            lane_local,
            mesh=bgrid.mesh,
            in_specs=(bgrid.spec, bgrid.spec, bgrid.aux_spec,
                      bgrid.spec, bgrid.batch_spec)
            + (bgrid.batch_spec,) * ndim,
            out_specs=bgrid.spec,
            check_vma=False,
        )
        return lambda s, P: (inner(*s, P[0], P[1], P[2], *P[3]), s[0])

    def run_vmem_resident(
        self, nt: int | None = None, warmup: int | None = None,
        chunk: int | None = None, config: str | None = None,
    ) -> WaveRunResult:
        """Single-shard fast path: the whole leapfrog loop inside one
        Pallas kernel, state pair VMEM-resident
        (ops.wave_kernels.wave_multi_step) — the wave edition of the
        diffusion flagship's schedule (HeatDiffusion.run_vmem_resident).
        `chunk` overrides the per-launch step count (the autotuner's
        measurement knob); `config="auto"` fills an unset chunk from the
        tuning cache (op "wave.vmem_loop") — resolved here, outside any
        trace, then gcd'd against the windows like every granularity.
        """
        from rocm_mpi_tpu.ops.pallas_kernels import DEFAULT_STEP_CHUNK
        from rocm_mpi_tpu.ops.wave_kernels import wave_multi_step

        cfg = self.config
        if self.grid.nprocs != 1:
            raise ValueError("the VMEM-resident path requires an unsharded grid")
        explicit = chunk is not None
        if config == "auto" and chunk is None:
            from rocm_mpi_tpu.ops.pallas_kernels import adoptable_vmem_chunk
            from rocm_mpi_tpu.tuning import resolve as tuning_resolve

            tuned = tuning_resolve.resolve(
                "wave.vmem_loop", cfg.global_shape, cfg.jax_dtype
            )
            if tuned and adoptable_vmem_chunk(tuned.get("chunk")):
                chunk = tuned["chunk"]
        elif config not in (None, "default", "auto"):
            raise ValueError(
                f"config must be None, 'default' or 'auto', got {config!r}"
            )
        chunk = effective_block_steps(
            cfg.nt if nt is None else nt,
            cfg.warmup if warmup is None else warmup,
            DEFAULT_STEP_CHUNK if chunk is None else chunk,
            warn=explicit, label="wave VMEM chunk",
        )
        dt = cfg.jax_dtype(cfg.dt)

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def advance(U, Uprev, C2, n):
            # warn_on_cap=False: the chunk is framework-plumbed here, not
            # caller-requested (same policy as diffusion _run_single_shard).
            return wave_multi_step(
                U, Uprev, C2, dt, cfg.spacing, n, chunk=chunk,
                warn_on_cap=False,
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
        """The sweep depth run_deep will actually execute for these
        arguments — THE source of truth for callers labeling artifacts by
        depth (apps/wave_2d.py), so label and executed k cannot drift.
        Policy (matching HeatDiffusion.effective_deep_depth, ADVICE r3):
        the DEFAULT depth clamps to the smallest shard extent (ghost
        slices need width <= shard); an EXPLICIT depth is first gcd'd
        against the windows (as diffusion's is) and raises only if the
        EFFECTIVE depth still exceeds the shard — the strict validation
        make_wave_deep_sweep applies, surfaced before any compile.
        """
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
            label="wave deep-halo sweep depth",
            warn=warn,
            stacklevel=3,
        )
        if explicit and eff > min(self.grid.local_shape):
            raise ValueError(
                f"wave deep-halo sweep depth {eff} exceeds a local "
                f"shard extent {self.grid.local_shape}; ghost slices need "
                "width <= shard"
            )
        return eff

    def _deep_schedule(self, k: int, wire_mode: str):
        """The wave deep schedule (parallel.deep_halo
        .make_wave_deep_sweep): one width-k ghost exchange of the
        leapfrog state pair per k steps, the second workload on the
        flagship multi-chip schedule; the time-invariant c² is exchanged
        and masked ONCE per compiled advance (DeepSchedule.prepare)."""
        from rocm_mpi_tpu.parallel.deep_halo import make_wave_deep_sweep

        cfg = self.config
        return make_wave_deep_sweep(self.grid, k, cfg.jax_dtype(cfg.dt),
                                    cfg.spacing, wire_mode=wire_mode)

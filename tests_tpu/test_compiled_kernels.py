"""Every Mosaic (compiled Pallas) path vs the jnp oracle, tiny shapes, f32.

Covers the kernel inventory the CPU suite can only interpret: whole-block,
striped (divisible + partial-stripe), kp 3-kernel, VMEM-resident multi-step,
temporal-blocked HBM sweep (2D + 3D), deep-halo local compute, the Cm
per-step family, the hide strip kernels, and the model-level runners.
Tolerances are f32-scale; the arithmetic is identical up to association so
agreement is ~1e-6 relative.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import rocm_mpi_tpu.ops.pallas_kernels as pk
from rocm_mpi_tpu.config import DiffusionConfig
from rocm_mpi_tpu.models import HeatDiffusion
from rocm_mpi_tpu.ops.diffusion import step_fused, step_fused_padded

RTOL, ATOL = 2e-5, 1e-6


def _rand(shape, seed=0):
    return jax.random.uniform(jax.random.PRNGKey(seed), shape, jnp.float32)


def _close(got, ref):
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=RTOL, atol=ATOL
    )


def test_backend_is_accelerated():
    assert jax.devices()[0].platform == "tpu"
    # interpret=None must resolve to compiled on this backend — otherwise
    # this whole tier silently tests the interpreter again.
    assert not pk._interpret_default()


def test_whole_block_compiled():
    Tp = _rand((34, 30))
    Cp = 1.0 + _rand((32, 28), seed=1)
    args = (1.3, 1e-4, (0.1, 0.07))
    _close(pk.fused_step_padded(Tp, Cp, *args), step_fused_padded(Tp, Cp, *args))


def test_striped_compiled(monkeypatch):
    monkeypatch.setattr(pk, "_VMEM_BLOCK_BUDGET_BYTES", 1024)
    Tp = _rand((66, 50))
    Cp = 1.0 + _rand((64, 48), seed=1)
    args = (1.0, 2e-4, (0.1, 0.1))
    _close(pk.fused_step_padded(Tp, Cp, *args), step_fused_padded(Tp, Cp, *args))


def test_striped_partial_stripe_compiled(monkeypatch):
    # Row count not a multiple of the stripe height: ceil grid + partial
    # trailing blocks must behave on Mosaic as in interpret mode.
    monkeypatch.setattr(pk, "_VMEM_BLOCK_BUDGET_BYTES", 1024)
    Tp = _rand((69, 50))
    Cp = 1.0 + _rand((67, 48), seed=1)
    args = (1.0, 2e-4, (0.1, 0.1))
    _close(pk.fused_step_padded(Tp, Cp, *args), step_fused_padded(Tp, Cp, *args))


def test_kp_three_kernel_compiled():
    Tp = _rand((34, 30))
    Cp = 1.0 + _rand((32, 28), seed=1)
    args = (1.3, 1e-4, (0.1, 0.07))
    _close(pk.kp_step_padded(Tp, Cp, *args), step_fused_padded(Tp, Cp, *args))


@pytest.mark.parametrize("form", ["eqc", "conly"])
def test_vmem_multi_step_compiled(form, monkeypatch):
    # Both equal-spacing body forms — the production 'eqc' and the pending
    # kernel-form A/B's 'conly' candidate — under ONE setup/oracle, so
    # flipping the default after the measurement carries zero Mosaic risk
    # and the two forms can never drift to different test conditions. The
    # rim assertion pins the bitwise Dirichlet hold (Cm==0 outside the
    # interior ⇒ rim frozen) against any Mosaic reassociation, matching
    # the CPU analog in tests/test_pallas_kernels.py.
    monkeypatch.setattr(pk, "EQC_BODY_FORM", form)
    T = _rand((32, 32))
    Cp = jnp.full((32, 32), 1.5, jnp.float32)
    args = (1.0, 1e-5, (0.1, 0.1))
    ref = T
    for _ in range(32):
        ref = step_fused(ref, Cp, *args)
    got = pk.fused_multi_step(T, Cp, *args, n_steps=32, chunk=16)
    _close(got, ref)
    rim = np.ones((32, 32), bool)
    rim[1:-1, 1:-1] = False
    np.testing.assert_array_equal(np.asarray(got)[rim], np.asarray(T)[rim])


def test_vmem_multi_step_pow2_pad_compiled(monkeypatch):
    # The padded-layout opt-in (VMEM_PAD_POW2, the chip A/B's pad_* rows):
    # a non-pow2 field pads to aligned axes, runs the same unrolled loop,
    # and slices back — must agree with the jnp oracle compiled.
    monkeypatch.setattr(pk, "VMEM_PAD_POW2", True)
    T = _rand((20, 24))
    Cp = 1.0 + _rand((20, 24), seed=1)
    args = (1.0, 1e-5, (0.1, 0.1))
    ref = T
    for _ in range(16):
        ref = step_fused(ref, Cp, *args)
    got = pk.fused_multi_step(T, Cp, *args, n_steps=16, chunk=8)
    assert got.shape == T.shape
    _close(got, ref)


def test_vmem_multi_step_unequal_spacing_compiled():
    # chunk >= 4 with unequal spacing: the general per-axis A/c branch
    # (equal spacing above takes the single-c specialization instead).
    T = _rand((32, 32))
    Cp = 1.0 + _rand((32, 32), seed=1)
    args = (1.0, 1e-5, (0.1, 0.07))
    ref = T
    for _ in range(16):
        ref = step_fused(ref, Cp, *args)
    _close(pk.fused_multi_step(T, Cp, *args, n_steps=16, chunk=8), ref)


def test_temporal_blocked_compiled():
    T = _rand((48, 48))
    Cp = 1.0 + _rand((48, 48), seed=1)
    args = (1.0, 1e-4, (0.5, 0.5))
    ref = T
    for _ in range(16):
        ref = step_fused(ref, Cp, *args)
    _close(pk.fused_multi_step_hbm(T, Cp, *args, 16, block_steps=8), ref)


def test_temporal_blocked_3d_compiled():
    T = _rand((32, 16, 128))
    Cp = 1.0 + _rand((32, 16, 128), seed=2)
    args = (0.8, 5e-5, (0.3, 0.4, 0.5))
    ref = T
    for _ in range(8):
        ref = step_fused(ref, Cp, *args)
    _close(pk.fused_multi_step_hbm(T, Cp, *args, 8, block_steps=4), ref)


def test_multi_step_cm_compiled():
    T = _rand((32, 32))
    Cp = 1.0 + _rand((32, 32), seed=1)
    lam, dt, spacing = 1.0, 1e-4, (0.1, 0.1)
    Cm = pk.edge_masked_cm(T, Cp, lam, dt)
    ref = T
    for _ in range(4):
        ref = step_fused(ref, Cp, lam, dt, spacing)
    _close(pk.multi_step_cm(T, Cm, spacing, 4), ref)


def test_fused_step_cm_whole_compiled():
    T = _rand((32, 28))
    Cp = 1.0 + _rand((32, 28), seed=1)
    lam, dt, spacing = 1.3, 1e-4, (0.1, 0.07)
    Cm = pk.edge_masked_cm(T, Cp, lam, dt)
    Tp = jnp.pad(T, ((1, 1), (1, 1)))
    _close(pk.fused_step_cm(Tp, Cm, spacing), step_fused(T, Cp, lam, dt, spacing))


def test_fused_step_cm_striped_compiled(monkeypatch):
    monkeypatch.setattr(pk, "_VMEM_BLOCK_BUDGET_BYTES", 1024)
    T = _rand((61, 48))
    Cp = 1.0 + _rand((61, 48), seed=1)
    lam, dt, spacing = 1.0, 2e-4, (0.1, 0.1)
    Cm = pk.edge_masked_cm(T, Cp, lam, dt)
    Tp = jnp.pad(T, ((1, 1), (1, 1)))
    _close(pk.fused_step_cm(Tp, Cm, spacing), step_fused(T, Cp, lam, dt, spacing))


@pytest.mark.parametrize("shape", [(64, 48), (16, 10, 8)])
def test_masked_step_striped_compiled(shape, monkeypatch):
    monkeypatch.setattr(pk, "_VMEM_BLOCK_BUDGET_BYTES", 1024)
    T = _rand(shape)
    Cp = 1.0 + _rand(shape, seed=1)
    lam, dt = 1.0, 2e-4
    spacing = (0.1,) * len(shape)
    Cm = pk.edge_masked_cm(T, Cp, lam, dt)
    _close(pk.masked_step(T, Cm, spacing), step_fused(T, Cp, lam, dt, spacing))


def test_masked_step_real_stripes_compiled():
    # Real dispatch (no budget shrink): 1024² f32 = 4 MB > the 2 MB budget
    # → the ghost-block striped per-step kernel at its production stripe
    # height, compiled.
    T = _rand((1024, 1024))
    Cp = 1.0 + _rand((1024, 1024), seed=1)
    lam, dt, spacing = 1.0, 1e-4, (0.01, 0.01)
    Cm = pk.edge_masked_cm(T, Cp, lam, dt)
    _close(pk.masked_step(T, Cm, spacing), step_fused(T, Cp, lam, dt, spacing))


def test_masked_step_bf16_stripes_compiled():
    # The bf16 precision-trade path: g=8 ghost blocks on (16,128)-tiled
    # bf16 must still compile and agree with the f32 oracle to bf16
    # precision (~0.4 % single-step). dt must respect the CFL bound
    # (min(d²)·Cp/λ/4.1 ≈ 2.4e-5 here): an unstable step amplifies the
    # bf16 rounding of the Laplacian beyond any fixed tolerance.
    T32 = _rand((2048, 2048))
    Cp = 1.0 + _rand((2048, 2048), seed=1)
    lam, dt, spacing = 1.0, 1e-5, (0.01, 0.01)
    Cm32 = pk.edge_masked_cm(T32, Cp, lam, dt)
    got = pk.masked_step(
        T32.astype(jnp.bfloat16), Cm32.astype(jnp.bfloat16), spacing
    )
    ref = step_fused(T32, Cp, lam, dt, spacing)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref), rtol=2e-2, atol=1e-2
    )


def test_hide_strip_kernels_compiled():
    # The hide variant's production strip combination — fused_step_cm per
    # region with mask_boundary=False (models.diffusion._make_hide_step's
    # compiled-dtype sharded branch) — under shard_map on a 1-device mesh:
    # compiles the Cm strip kernels on the slab shapes even though
    # multi-chip hardware isn't available here.
    from jax import shard_map

    from rocm_mpi_tpu.parallel.mesh import init_global_grid
    from rocm_mpi_tpu.parallel.overlap import make_overlap_step

    grid = init_global_grid(48, 48, dims=(1, 1), devices=jax.devices()[:1])
    pu = lambda tp, cm, lam, dt, spacing: pk.fused_step_cm(tp, cm, spacing)
    local = make_overlap_step(grid, pu, (8, 8), mask_boundary=False)
    lam, dt, spacing = 1.0, 1e-4, grid.spacing
    T = _rand((48, 48))
    Cp = 1.0 + _rand((48, 48), seed=1)
    Cm = pk.edge_masked_cm(T, Cp, lam, dt)

    @jax.jit
    def step(T, Cm):
        return shard_map(
            lambda Tl, Cml: local(Tl, Cml, lam, dt, spacing),
            mesh=grid.mesh,
            in_specs=(grid.spec, grid.spec),
            out_specs=grid.spec,
            check_vma=False,
        )(T, Cm)

    _close(step(T, Cm), step_fused(T, Cp, lam, dt, spacing))


def test_hide_strip_kernels_narrow_slabs_compiled():
    # b_width=1 boundary slabs: 1-row/1-column region blocks are the
    # nastiest shapes Mosaic sees from the overlap ladder.
    from jax import shard_map

    from rocm_mpi_tpu.parallel.mesh import init_global_grid
    from rocm_mpi_tpu.parallel.overlap import make_overlap_step

    grid = init_global_grid(32, 32, dims=(1, 1), devices=jax.devices()[:1])
    pu = lambda tp, cm, lam, dt, spacing: pk.fused_step_cm(tp, cm, spacing)
    local = make_overlap_step(grid, pu, (1, 1), mask_boundary=False)
    lam, dt, spacing = 1.0, 1e-4, grid.spacing
    T = _rand((32, 32))
    Cp = 1.0 + _rand((32, 32), seed=1)
    Cm = pk.edge_masked_cm(T, Cp, lam, dt)

    @jax.jit
    def step(T, Cm):
        return shard_map(
            lambda Tl, Cml: local(Tl, Cml, lam, dt, spacing),
            mesh=grid.mesh,
            in_specs=(grid.spec, grid.spec),
            out_specs=grid.spec,
            check_vma=False,
        )(T, Cm)

    _close(step(T, Cm), step_fused(T, Cp, lam, dt, spacing))


def test_deep_halo_sweep_compiled():
    from rocm_mpi_tpu.parallel.deep_halo import make_deep_sweep
    from rocm_mpi_tpu.parallel.mesh import init_global_grid

    grid = init_global_grid(64, 64, dims=(1, 1), devices=jax.devices()[:1])
    lam, dt = 1.0, jnp.float32(1e-4)
    sched = make_deep_sweep(grid, 4, lam, dt, grid.spacing)
    T = _rand((64, 64))
    Cp = 1.0 + _rand((64, 64), seed=1)
    Cm = jax.jit(sched.prepare)(Cp)  # the once-per-advance Cp exchange
    ref = T
    for _ in range(4):
        ref = step_fused(ref, Cp, lam, dt, grid.spacing)
    _close(jax.jit(sched.sweep)(T, Cm), ref)


def test_deep_halo_hbm_shard_compiled():
    # Real dispatch: a 736² f32 shard pads to 752² = 2.26 MB > the VMEM
    # budget → the deep sweep's local compute is the temporal-blocked HBM
    # sweep (multi_step_cm_hbm), compiled.
    from rocm_mpi_tpu.parallel.deep_halo import make_deep_sweep
    from rocm_mpi_tpu.parallel.mesh import init_global_grid

    grid = init_global_grid(736, 736, dims=(1, 1), devices=jax.devices()[:1])
    lam, dt = 1.0, jnp.float32(1e-5)
    sched = make_deep_sweep(grid, 8, lam, dt, grid.spacing)
    T = _rand((736, 736))
    Cp = 1.0 + _rand((736, 736), seed=1)
    Cm = jax.jit(sched.prepare)(Cp)
    ref = T
    for _ in range(8):
        ref = step_fused(ref, Cp, lam, dt, grid.spacing)
    _close(jax.jit(sched.sweep)(T, Cm), ref)


def test_wave_kernel_compiled():
    # Second workload's Pallas kernel (ops.wave_kernels) vs its jnp twin.
    from rocm_mpi_tpu.ops.wave_kernels import (
        wave_step_padded,
        wave_step_padded_pallas,
    )

    Up = _rand((34, 30))
    Uprev = _rand((32, 28), seed=1)
    C2 = 1.0 + _rand((32, 28), seed=2)
    dt, spacing = 1e-3, (0.1, 0.07)
    _close(
        wave_step_padded_pallas(Up, Uprev, C2, dt, spacing),
        wave_step_padded(Up, Uprev, C2, dt, spacing),
    )


def test_wave_vmem_multi_step_compiled():
    # The whole-loop-in-VMEM leapfrog, compiled, vs the jnp per-step form.
    from rocm_mpi_tpu.models.wave import wave_step_fused
    from rocm_mpi_tpu.ops.wave_kernels import wave_multi_step

    U0 = _rand((32, 32))
    C2 = 1.0 + _rand((32, 32), seed=1)
    dt, spacing = 2e-3, (0.1, 0.1)
    ref, ref_prev = U0, jnp.copy(U0)
    for _ in range(16):
        ref, ref_prev = wave_step_fused(ref, ref_prev, C2, dt, spacing), ref
    got, got_prev = wave_multi_step(
        U0, jnp.copy(U0), C2, dt, spacing, 16, chunk=8
    )
    _close(got, ref)
    _close(got_prev, ref_prev)


def test_wave_deep_sweep_compiled():
    # The wave deep-halo sweep's masked VMEM kernel on a 1-device mesh.
    from rocm_mpi_tpu.models.wave import AcousticWave, WaveConfig
    from rocm_mpi_tpu.parallel.deep_halo import make_wave_deep_sweep

    cfg = WaveConfig(
        global_shape=(64, 64), lengths=(10.0, 10.0), nt=8, warmup=0,
        dtype="f32", dims=(1, 1),
    )
    model = AcousticWave(cfg, devices=jax.devices()[:1])
    U, Uprev, C2 = model.init_state()
    ref, _ = model.advance_fn("ap")(jnp.copy(U), jnp.copy(Uprev), C2, 8)
    sched = make_wave_deep_sweep(
        model.grid, 4, cfg.jax_dtype(cfg.dt), cfg.spacing
    )
    P = jax.jit(sched.prepare)(C2)
    sweep = jax.jit(sched.sweep)
    got, _ = sweep(*sweep(U, Uprev, P), P)
    _close(got, ref)


def test_temporal_blocked_k16_geometry_compiled():
    # r4: the deeper (g=16, tm=32) sweep geometry — 64-row slabs, 16
    # unrolled steps — must compile on Mosaic at narrow widths (wide rows
    # are envelope-gated; the width boundary itself is measured by
    # scripts/bench_tb_stripes.py, not asserted here).
    T32 = _rand((64, 48))
    Cp32 = 1.0 + _rand((64, 48), seed=1)
    lam, dt, spacing = 1.0, 1e-4, (0.1, 0.1)
    ref = T32
    for _ in range(16):
        ref = step_fused(ref, Cp32, lam, dt, spacing)
    got = pk.fused_multi_step_hbm(
        T32, Cp32, lam, dt, spacing, 16, block_steps=16
    )
    _close(got, ref)


def test_bf16_storage_only_multi_step_compiled():
    # r4: bf16 operands upcast to f32 inside the kernel and round back
    # once per chunk (storage-only bf16). New Mosaic surface: the
    # convert_element_type pair inside the unrolled VMEM loop must
    # compile, and the result must track the f32 trajectory to bf16
    # resolution instead of freezing (the per-step-rounding failure mode
    # documented in docs/bf16_error_cpu252_perstep_r4.txt).
    T32 = _rand((64, 64))
    Cp32 = 1.0 + _rand((64, 64), seed=1)
    lam, dt, spacing = 1.0, 1e-4, (0.1, 0.1)
    ref = pk.fused_multi_step(T32, Cp32, lam, dt, spacing, 64, chunk=16)
    got16 = pk.fused_multi_step(
        T32.astype(jnp.bfloat16), Cp32.astype(jnp.bfloat16),
        lam, dt, spacing, 64, chunk=16,
    )
    assert got16.dtype == jnp.bfloat16  # rounds back to storage dtype
    np.testing.assert_allclose(
        np.asarray(got16, np.float32), np.asarray(ref), rtol=0.02,
        atol=0.02,
    )


def test_bf16_storage_only_tb_sweep_compiled():
    # The temporal-blocked edition: bf16 slabs, f32 sweep arithmetic,
    # one rounding per k-step sweep (the suite's bf16 tb row).
    T32 = _rand((64, 48))
    Cp32 = 1.0 + _rand((64, 48), seed=1)
    lam, dt, spacing = 1.0, 1e-4, (0.1, 0.1)
    ref = pk.fused_multi_step_hbm(
        T32, Cp32, lam, dt, spacing, 32, block_steps=8
    )
    got16 = pk.fused_multi_step_hbm(
        T32.astype(jnp.bfloat16), Cp32.astype(jnp.bfloat16),
        lam, dt, spacing, 32, block_steps=8,
    )
    assert got16.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got16, np.float32), np.asarray(ref), rtol=0.02,
        atol=0.02,
    )


def test_wave_hide_strip_kernels_compiled():
    # The wave hide variant's production strip combination (r4): the
    # 3-operand leapfrog Pallas kernel per region with (U_prev, C2) as
    # core-only aux pytree — under shard_map on a 1-device mesh, so the
    # slab-shaped wave kernels compile on the chip even though the
    # sharded hide path needs >= 2 devices to be selected organically.
    from jax import shard_map

    from rocm_mpi_tpu.models.wave import wave_step_fused
    from rocm_mpi_tpu.ops.wave_kernels import wave_step_padded_pallas
    from rocm_mpi_tpu.parallel.mesh import init_global_grid
    from rocm_mpi_tpu.parallel.overlap import make_overlap_step

    grid = init_global_grid(48, 48, dims=(1, 1), devices=jax.devices()[:1])
    dt, spacing = 1e-3, grid.spacing

    def pu(tp, aux, lam, dt_, sp):
        del lam
        return wave_step_padded_pallas(tp, aux[0], aux[1], dt_, sp)

    local = make_overlap_step(grid, pu, (8, 8))
    U = _rand((48, 48))
    Uprev = _rand((48, 48), seed=1)
    C2 = 1.0 + _rand((48, 48), seed=2)

    @jax.jit
    def step(U, Uprev, C2):
        return shard_map(
            lambda Ul, Upl, C2l: local(Ul, (Upl, C2l), None, dt, spacing),
            mesh=grid.mesh,
            in_specs=(grid.spec,) * 3,
            out_specs=grid.spec,
            check_vma=False,
        )(U, Uprev, C2)

    _close(step(U, Uprev, C2), wave_step_fused(U, Uprev, C2, dt, spacing))


def test_model_runners_compiled():
    # The model-level fast paths end-to-end on the chip at tiny sizes.
    cfg = DiffusionConfig(
        global_shape=(64, 64), lengths=(10.0, 10.0), nt=32, warmup=8,
        dtype="f32", dims=(1, 1),
    )
    model = HeatDiffusion(cfg)
    r_perf = model.run(variant="perf")
    r_hide = model.run(variant="hide")
    r_vmem = model.run_vmem_resident()
    r_deep = model.run_deep(block_steps=8)
    r_tb = model.run_hbm_blocked(block_steps=8)
    np.testing.assert_array_equal(np.asarray(r_hide.T), np.asarray(r_perf.T))
    for r in (r_vmem, r_deep, r_tb):
        _close(r.T, r_perf.T)


def test_swe_padded_kernel_compiled():
    # Third workload (r4): the coupled padded SWE kernel vs its jnp twin.
    from rocm_mpi_tpu.ops.swe_kernels import (
        swe_step_padded,
        swe_step_padded_pallas,
    )

    hp = _rand((34, 30))
    ups = (_rand((34, 30), seed=1), _rand((34, 30), seed=2))
    Mus = (jnp.ones((32, 28), jnp.float32), jnp.ones((32, 28), jnp.float32))
    consts, dt, spacing = (1.0, 1.0), 1e-3, (0.1, 0.07)
    got = swe_step_padded_pallas((hp,) + ups, Mus, consts, dt, spacing)
    ref = swe_step_padded((hp,) + ups, Mus, consts, dt, spacing)
    for g, r in zip(got, ref):
        _close(g, r)


def test_swe_vmem_multi_step_compiled():
    # The whole-loop-in-VMEM coupled multi-step, compiled, vs the jnp
    # roll form (masked_swe_step — the one definition of the update).
    from rocm_mpi_tpu.ops.swe_kernels import (
        masked_swe_step,
        swe_coeffs,
        swe_multi_step,
    )

    h0 = _rand((32, 32))
    us0 = (jnp.zeros((32, 32), jnp.float32),) * 2
    gidx0 = jax.lax.broadcasted_iota(jnp.int32, (32, 32), 0)
    gidx1 = jax.lax.broadcasted_iota(jnp.int32, (32, 32), 1)
    Mus = (
        jnp.where(gidx0 >= 31, 0.0, 1.0).astype(jnp.float32),
        jnp.where(gidx1 >= 31, 0.0, 1.0).astype(jnp.float32),
    )
    dt, spacing = 2e-3, (0.1, 0.1)
    cH, cg = swe_coeffs(dt, spacing, 1.0, 1.0)
    ref_h, ref_us = h0, us0
    for _ in range(16):
        ref_h, ref_us = masked_swe_step(ref_h, ref_us, Mus, cH, cg)
    got_h, got_us = swe_multi_step(
        h0, us0, Mus, dt, spacing, 1.0, 1.0, 16, chunk=8
    )
    _close(got_h, ref_h)
    for g, r in zip(got_us, ref_us):
        _close(g, r)


def test_swe_deep_sweep_compiled():
    # The SWE deep-halo sweep's masked VMEM kernel on a 1-device mesh.
    from rocm_mpi_tpu.models.swe import SWEConfig, ShallowWater
    from rocm_mpi_tpu.parallel.deep_halo import make_swe_deep_sweep

    cfg = SWEConfig(
        global_shape=(64, 64), lengths=(10.0, 10.0), nt=8, warmup=0,
        dtype="f32", dims=(1, 1),
    )
    model = ShallowWater(cfg, devices=jax.devices()[:1])
    h, us = model.init_state()
    Mus = model.face_masks()
    ref_h, ref_us = model.advance_fn("ap")(
        jnp.copy(h), tuple(map(jnp.copy, us)), Mus, 8
    )
    sched = make_swe_deep_sweep(model.grid, 4, cfg.dt, cfg.spacing,
                                cfg.H0, cfg.g)
    P = jax.jit(sched.prepare)(h)
    sweep = jax.jit(sched.sweep)
    got_h, got_us = sweep(*sweep(h, us, P), P)
    _close(got_h, ref_h)
    for gu, ru in zip(got_us, ref_us):
        _close(gu, ru)


def test_swe_hide_strip_kernels_compiled():
    # The SWE hide variant's strip combination: the pytree-state overlap
    # decomposition with the coupled padded Pallas kernel per region —
    # under shard_map on a 1-device mesh, so the slab-shaped SWE kernels
    # compile on the chip even though the sharded hide path needs >= 2
    # devices to be selected organically.
    from jax import shard_map

    from rocm_mpi_tpu.ops.swe_kernels import (
        swe_step_padded,
        swe_step_padded_pallas,
    )
    from rocm_mpi_tpu.parallel.mesh import init_global_grid
    from rocm_mpi_tpu.parallel.overlap import make_overlap_step

    grid = init_global_grid(48, 48, dims=(1, 1), devices=jax.devices()[:1])
    dt, spacing = 1e-3, grid.spacing
    consts = (1.0, 1.0)

    def pu(Sp, Ml, lam, dt_, sp):
        del lam
        return swe_step_padded_pallas(Sp, Ml, consts, dt_, sp)

    local = make_overlap_step(grid, pu, (8, 8), mask_boundary=False)
    h = _rand((48, 48))
    us = (_rand((48, 48), seed=1), _rand((48, 48), seed=2))
    gi0 = jax.lax.broadcasted_iota(jnp.int32, (48, 48), 0)
    gi1 = jax.lax.broadcasted_iota(jnp.int32, (48, 48), 1)
    Mus = (
        jnp.where(gi0 >= 47, 0.0, 1.0).astype(jnp.float32),
        jnp.where(gi1 >= 47, 0.0, 1.0).astype(jnp.float32),
    )

    @jax.jit
    def step(h, u0, u1, M0, M1):
        return shard_map(
            lambda hl, u0l, u1l, M0l, M1l: local(
                (hl, u0l, u1l), (M0l, M1l), None, dt, spacing
            ),
            mesh=grid.mesh,
            in_specs=(grid.spec,) * 5,
            out_specs=(grid.spec,) * 3,
            check_vma=False,
        )(h, u0, u1, M0, M1)

    got = step(h, us[0], us[1], Mus[0], Mus[1])
    # Referee: the jnp padded form on the zero-padded whole block (the
    # 1-device ghost convention).
    pad = [(1, 1)] * 2
    Sp = tuple(jnp.pad(f, pad) for f in (h,) + us)
    ref = swe_step_padded(Sp, Mus, consts, dt, spacing)
    for g, r in zip(got, ref):
        _close(g, r)

"""Shallow-water workload (models.swe): numpy oracle, EXACT mass
conservation, algebraic time reversal, cross-variant and sharding
equivalence — the correctness strategy of the diffusion/wave suites
applied to the third workload, whose coupled ndim+1-field state is what
exercises the pytree-state paths of parallel.overlap and
parallel.deep_halo."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rocm_mpi_tpu.models.swe import SWEConfig, ShallowWater
from rocm_mpi_tpu.ops.swe_kernels import swe_coeffs


def _cfg(shape=(24, 20), dims=(1, 1), dtype="f64", nt=40, warmup=8):
    return SWEConfig(
        global_shape=shape,
        lengths=tuple(10.0 for _ in shape),
        nt=nt,
        warmup=warmup,
        dtype=dtype,
        dims=dims,
    )


def _numpy_fb(h, us, dt, spacing, H, g, n):
    """Transparent numpy oracle of the forward-backward C-grid update:
    backward-difference divergence into h, then forward-difference
    gradient of the NEW h into each velocity, with the high wall face
    along each axis held at 0 and zero beyond-domain values."""
    h = np.array(h, np.float64)
    us = [np.array(u, np.float64) for u in us]
    ndim = h.ndim
    for _ in range(n):
        div = np.zeros_like(h)
        for a, u in enumerate(us):
            um = np.zeros_like(u)  # u shifted +1 along a, zero-filled
            lo = tuple(
                slice(1, None) if ax == a else slice(None)
                for ax in range(ndim)
            )
            hi = tuple(
                slice(None, -1) if ax == a else slice(None)
                for ax in range(ndim)
            )
            um[lo] = u[hi]
            div += (u - um) * (dt * H / spacing[a])
        h = h - div
        for a in range(ndim):
            hp = np.zeros_like(h)  # h shifted −1 along a, zero-filled
            lo = tuple(
                slice(None, -1) if ax == a else slice(None)
                for ax in range(ndim)
            )
            hi = tuple(
                slice(1, None) if ax == a else slice(None)
                for ax in range(ndim)
            )
            hp[lo] = h[hi]
            us[a] = us[a] - (dt * g / spacing[a]) * (hp - h)
            # hold the high wall face
            wall = tuple(
                slice(-1, None) if ax == a else slice(None)
                for ax in range(ndim)
            )
            us[a][wall] = 0.0
    return h, us


def _advance(model, variant, n):
    h, us = model.init_state()
    Mus = model.face_masks()
    return model.advance_fn(variant)(h, us, Mus, n)


def test_swe_matches_numpy_oracle():
    cfg = _cfg()
    model = ShallowWater(cfg, devices=jax.devices()[:1])
    h0, us0 = model.init_state()
    ref_h, ref_us = _numpy_fb(
        h0, us0, cfg.dt, cfg.spacing, cfg.H0, cfg.g, 25
    )
    got_h, got_us = model.advance_fn("ap")(h0, us0, model.face_masks(), 25)
    np.testing.assert_allclose(np.asarray(got_h), ref_h, rtol=1e-12)
    for got_u, ref_u in zip(got_us, ref_us):
        np.testing.assert_allclose(
            np.asarray(got_u), ref_u, rtol=1e-12, atol=1e-15
        )


def test_swe_mass_exactly_conserved():
    # The closed-basin divergence telescopes to wall−wall = 0, so sum(h)
    # is invariant to fp rounding — the workload's exact invariant.
    cfg = _cfg(nt=200, warmup=0)
    model = ShallowWater(cfg, devices=jax.devices()[:1])
    h0, us0 = model.init_state()
    mass0 = float(jnp.sum(h0, dtype=jnp.float64))
    got_h, _ = model.advance_fn("ap")(h0, us0, model.face_masks(), 200)
    mass = float(jnp.sum(got_h, dtype=jnp.float64))
    assert abs(mass - mass0) <= 1e-13 * abs(mass0)


def test_swe_mass_conserved_sharded_all_variants():
    for variant in ("ap", "perf", "hide"):
        cfg = _cfg(shape=(32, 32), dims=(2, 4), nt=64, warmup=0)
        model = ShallowWater(cfg)
        h0, us0 = model.init_state()
        mass0 = float(jnp.sum(h0, dtype=jnp.float64))
        got_h, _ = model.advance_fn(variant)(
            h0, us0, model.face_masks(), 64
        )
        mass = float(jnp.sum(got_h, dtype=jnp.float64))
        assert abs(mass - mass0) <= 1e-13 * abs(mass0), variant


def test_swe_wall_faces_stay_zero():
    cfg = _cfg()
    model = ShallowWater(cfg, devices=jax.devices()[:1])
    _, got_us = _advance(model, "ap", 30)
    for a, u in enumerate(got_us):
        wall = tuple(
            slice(-1, None) if ax == a else slice(None)
            for ax in range(cfg.ndim)
        )
        np.testing.assert_array_equal(np.asarray(u)[wall], 0.0)


def test_swe_time_reversal_algebraic():
    # The forward-backward map has a closed-form inverse (inverse
    # sub-steps in reverse order); running it returns the IC at rounding
    # level — the symplectic-structure analog of the wave's leapfrog
    # reversal test.
    cfg = _cfg(nt=60)
    model = ShallowWater(cfg, devices=jax.devices()[:1])
    h0, us0 = model.init_state()
    Mus = model.face_masks()
    n = 40
    h, us = model.advance_fn("ap")(
        jnp.copy(h0), tuple(map(jnp.copy, us0)), Mus, n
    )
    cH, cg = swe_coeffs(cfg.dt, cfg.spacing, cfg.H0, cfg.g)

    def inverse_step(h, us):
        us = tuple(
            u + cg[a] * Mus[a] * (jnp.roll(h, -1, a) - h)
            for a, u in enumerate(us)
        )
        div = sum(
            cH[a] * (u - jnp.roll(u, 1, a)) for a, u in enumerate(us)
        )
        return h + div, us

    for _ in range(n):
        h, us = inverse_step(h, us)
    np.testing.assert_allclose(
        np.asarray(h), np.asarray(h0), rtol=1e-11, atol=1e-13
    )
    for u, u0 in zip(us, us0):
        np.testing.assert_allclose(
            np.asarray(u), np.asarray(u0), atol=1e-13
        )


@pytest.mark.parametrize("dtype", ["f64", "f32"])
def test_swe_perf_matches_ap(dtype):
    tol = 1e-12 if dtype == "f64" else 2e-6
    cfg = _cfg(dtype=dtype)
    model = ShallowWater(cfg, devices=jax.devices()[:1])
    ap_h, ap_us = _advance(model, "ap", 30)
    pf_h, pf_us = _advance(model, "perf", 30)
    np.testing.assert_allclose(
        np.asarray(pf_h), np.asarray(ap_h), rtol=tol, atol=tol
    )
    for pu, au in zip(pf_us, ap_us):
        np.testing.assert_allclose(
            np.asarray(pu), np.asarray(au), rtol=tol, atol=tol
        )


def test_swe_sharded_matches_single_device():
    single = ShallowWater(_cfg(shape=(32, 32)), devices=jax.devices()[:1])
    truth_h, truth_us = _advance(single, "perf", 30)
    for dims in [(2, 2), (4, 2), (1, 8)]:
        model = ShallowWater(_cfg(shape=(32, 32), dims=dims))
        got_h, got_us = _advance(model, "perf", 30)
        np.testing.assert_allclose(
            np.asarray(got_h), np.asarray(truth_h), rtol=1e-12, atol=1e-14
        )
        for gu, tu in zip(got_us, truth_us):
            np.testing.assert_allclose(
                np.asarray(gu), np.asarray(tu), rtol=1e-12, atol=1e-14
            )


def test_swe_hide_matches_perf_sharded():
    for dims in [(2, 2), (2, 4)]:
        cfg = _cfg(shape=(32, 32), dims=dims)
        model = ShallowWater(cfg)
        pf_h, pf_us = _advance(model, "perf", 30)
        hd_h, hd_us = _advance(model, "hide", 30)
        np.testing.assert_allclose(
            np.asarray(hd_h), np.asarray(pf_h), rtol=1e-12, atol=1e-14
        )
        for hu, pu in zip(hd_us, pf_us):
            np.testing.assert_allclose(
                np.asarray(hu), np.asarray(pu), rtol=1e-12, atol=1e-14
            )


def test_swe_hide_3d_matches_perf():
    cfg = _cfg(shape=(12, 12, 12), dims=(2, 2, 2), nt=12, warmup=0)
    model = ShallowWater(cfg)
    pf_h, _ = _advance(model, "perf", 10)
    hd_h, _ = _advance(model, "hide", 10)
    np.testing.assert_allclose(
        np.asarray(hd_h), np.asarray(pf_h), rtol=1e-12, atol=1e-14
    )


def test_swe_deep_sweep_matches_per_step():
    single = ShallowWater(_cfg(shape=(32, 32)), devices=jax.devices()[:1])
    truth_h, truth_us = _advance(single, "ap", 32)
    for dims, k in [((2, 2), 4), ((2, 4), 8), ((1, 1), 4)]:
        model = ShallowWater(_cfg(shape=(32, 32), dims=dims))
        r = model.run_deep(nt=32, warmup=0, block_steps=k)
        np.testing.assert_allclose(
            np.asarray(r.h), np.asarray(truth_h), rtol=1e-12, atol=1e-14
        )
        for gu, tu in zip(r.us, truth_us):
            np.testing.assert_allclose(
                np.asarray(gu), np.asarray(tu), rtol=1e-12, atol=1e-14
            )


def test_swe_run_vmem_resident_matches_per_step():
    single = ShallowWater(_cfg(shape=(32, 32)), devices=jax.devices()[:1])
    truth_h, _ = _advance(single, "ap", 32)
    r = single.run_vmem_resident(nt=32, warmup=0, chunk=8)
    np.testing.assert_allclose(
        np.asarray(r.h), np.asarray(truth_h), rtol=1e-12, atol=1e-14
    )


def test_swe_explicit_oversized_deep_depth_raises():
    model = ShallowWater(_cfg(shape=(32, 32), dims=(2, 4)))
    with pytest.raises(ValueError, match="exceeds a local shard extent"):
        model.run_deep(nt=64, warmup=0, block_steps=64)


def test_swe_hide_single_device_routes_to_perf():
    cfg = _cfg()
    model = ShallowWater(cfg, devices=jax.devices()[:1])
    pf_h, _ = _advance(model, "perf", 20)
    hd_h, _ = _advance(model, "hide", 20)
    # Bit-identical: the single-device hide IS the perf program.
    np.testing.assert_array_equal(np.asarray(hd_h), np.asarray(pf_h))


def test_swe_run_reports_metrics():
    model = ShallowWater(_cfg(nt=16, warmup=4), devices=jax.devices()[:1])
    r = model.run("perf")
    assert r.wtime > 0 and r.t_eff > 0 and r.gpts > 0
    assert r.nt == 16 and r.warmup == 4


def test_swe_app_runs(tmp_path):
    import subprocess
    import sys

    out = tmp_path / "h.npy"
    proc = subprocess.run(
        [
            sys.executable, "apps/swe_2d.py", "--cpu-devices", "4",
            "--nx", "32", "--ny", "32", "--nt", "24", "--warmup", "4",
            "--save-field", str(out),
        ],
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr
    assert "mass drift" in proc.stdout
    h = np.load(out)
    assert h.shape == (32, 32)


def test_swe_bf16_rounding_is_per_kernel_not_per_step():
    """Storage-only bf16 holds for the SWE multi-step kernel too: the
    traced kernel contains exactly 2·(ndim+1)+ndim dtype conversions for
    bf16 operands — each state field in and out, each mask in —
    INDEPENDENT of the unroll (the diffusion mechanical pin,
    test_bf16_error.py, applied to the coupled workload)."""
    from rocm_mpi_tpu.ops.swe_kernels import swe_multi_step_masked

    h = jnp.zeros((32, 32), jnp.bfloat16)
    us = (jnp.zeros((32, 32), jnp.bfloat16),) * 2
    Mus = (jnp.ones((32, 32), jnp.bfloat16),) * 2
    cH = cg = (1e-3, 1e-3)
    counts = {
        n: str(
            jax.make_jaxpr(
                lambda h, us, Mus: swe_multi_step_masked(
                    h, us, Mus, cH, cg, n
                )
            )(h, us, Mus)
        ).count("convert_element_type")
        for n in (4, 16)
    }
    # 3 state in + 2 masks in + 3 state out = 8, whatever the unroll.
    assert counts[4] == counts[16] == 8, counts


# ---- the one-chip perf step: masked_swe_step's striped sweep -------------

# (shape, stripe rows): several whole stripes; a partial last stripe
# (4 rows of 16, so the row below the last is past the domain); a grid of
# one stripe taller than the grid; 8-row stripes over 5 rows at the end.
STRIPES = {
    "several": ((64, 40), 16),
    "partial": ((68, 40), 16),
    "one": ((20, 24), 32),
    "thin": ((37, 130), 8),
}


def _stripes(monkeypatch, tm):
    """masked_swe_step takes `tm`-row stripes whatever the grid."""
    from rocm_mpi_tpu.ops import swe_kernels

    monkeypatch.setattr(swe_kernels, "_swe_stripe_height",
                        lambda n0, *row_bytes: tm)


def _seeded_state(shape, dtype, seed):
    """(h, (u, v), (Mu, Mv)): h about 1 plus noise, u and v noise with
    the wall faces exactly 0, the closed basin's face masks."""
    rng = np.random.default_rng(seed)
    Mu = np.ones(shape)
    Mu[-1, :] = 0.0
    Mv = np.ones(shape)
    Mv[:, -1] = 0.0
    h = 1.0 + rng.uniform(size=shape)
    u = Mu * rng.uniform(-0.5, 0.5, size=shape)
    v = Mv * rng.uniform(-0.5, 0.5, size=shape)
    return tuple(jnp.asarray(a, dtype) for a in (h, u, v, Mu, Mv))


def _kernel_steps(shape, dtype, seed, n):
    from rocm_mpi_tpu.ops.swe_kernels import masked_swe_step, pack_walls

    cfg = _cfg(shape=shape)
    cH, cg = swe_coeffs(cfg.dt, cfg.spacing, cfg.H0, cfg.g)
    h, u, v, Mu, Mv = _seeded_state(shape, dtype, seed)
    walls = pack_walls((Mu, Mv))
    state = (h, (u, v))
    for _ in range(n):
        state = masked_swe_step(*state, walls, cH, cg)
    return cfg, _seeded_state(shape, dtype, seed), state


@pytest.mark.parametrize("case", sorted(STRIPES))
def test_masked_swe_step_matches_plain_reference(monkeypatch, case):
    """f32, against the benchmark's plain reference (jnp, its own
    operation order) over 20 steps. Tolerance: 4 roundings of the
    largest field a step (the kernel folds dt·H/dx into one constant,
    the reference divides), 4·n·eps32·max|field|, about 1e-5; a state
    stored in bf16 is off by 2^-9 of it at once, so it fails (checked)."""
    from benchmark.references import swe2d as reference

    shape, tm = STRIPES[case]
    _stripes(monkeypatch, tm)
    n = 20
    cfg, (h, u, v, Mu, Mv), (kh, (ku, kv)) = _kernel_steps(
        shape, jnp.float32, 3, n)
    config = {"global_shape": list(shape), "lengths": list(cfg.lengths),
              "H0": cfg.H0, "g": cfg.g, "cfl": cfg.cfl}
    ref = reference.advance_fn(config, n, jnp.float32)
    rh, (ru, rv) = ref((h, (u, v)), (Mu, Mv))
    tol = 4 * n * float(jnp.finfo(jnp.float32).eps) * float(jnp.max(h))
    for got, want in ((kh, rh), (ku, ru), (kv, rv)):
        assert float(jnp.max(jnp.abs(got - want))) <= tol
    bf16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16).astype(
        jnp.float32), (h, (u, v)))
    bh, _ = ref(bf16, (Mu, Mv))
    assert float(jnp.max(jnp.abs(bh - rh))) > tol


@pytest.mark.parametrize("case", sorted(STRIPES))
def test_masked_swe_step_matches_numpy_oracle(monkeypatch, case):
    """f64 in interpret mode, against the transparent numpy oracle over
    25 steps, to rounding (rtol 1e-12, as the ap variant's own test)."""
    shape, tm = STRIPES[case]
    _stripes(monkeypatch, tm)
    cfg, (h, u, v, *_), (kh, kus) = _kernel_steps(shape, jnp.float64, 5,
                                                  25)
    ref_h, ref_us = _numpy_fb(h, (u, v), cfg.dt, cfg.spacing, cfg.H0,
                              cfg.g, 25)
    np.testing.assert_allclose(np.asarray(kh), ref_h, rtol=1e-12)
    for got, want in zip(kus, ref_us):
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-12,
                                   atol=1e-15)


def test_swe_one_chip_perf_is_one_kernel_without_exchange(monkeypatch):
    """On one device the perf step is masked_swe_step alone: no pad of
    the state into a ghost-ringed buffer, one kernel a step."""
    from rocm_mpi_tpu.parallel import halo

    def no_exchange(*args, **kwargs):
        raise AssertionError("the one-chip perf step exchanged a halo")

    monkeypatch.setattr(halo, "exchange_halo", no_exchange)
    monkeypatch.setattr("rocm_mpi_tpu.models.swe.exchange_halo",
                        no_exchange)
    model = ShallowWater(_cfg(shape=(48, 40), dtype="f32"),
                         devices=jax.devices()[:1])
    h, us = model.init_state()
    text = str(jax.make_jaxpr(model.advance_fn("perf"))(
        h, us, model.face_masks(), 4))
    assert text.count("pallas_call") == 1
    assert "masked_swe_step" in text
    assert " pad" not in text


@pytest.mark.parametrize("case", ["several", "partial"])
@pytest.mark.parametrize("invariant", ["mass", "walls", "reversal"])
def test_swe_one_chip_perf_invariants(monkeypatch, case, invariant):
    """The workload's invariants on the one-chip perf path, striped:
    exact mass conservation, wall faces exactly 0, and the closed-form
    inverse running the state back to where it started."""
    shape, tm = STRIPES[case]
    _stripes(monkeypatch, tm)
    n = 40
    cfg = _cfg(shape=shape, nt=n, warmup=0)
    model = ShallowWater(cfg, devices=jax.devices()[:1])
    h0, u0, v0, Mu, Mv = _seeded_state(shape, jnp.float64, 11)
    Mus = (Mu, Mv)
    h, us = model.advance_fn("perf")(jnp.copy(h0), (jnp.copy(u0),
                                                    jnp.copy(v0)), Mus, n)
    if invariant == "mass":
        mass0 = float(jnp.sum(h0))
        assert abs(float(jnp.sum(h)) - mass0) <= 1e-13 * abs(mass0)
    elif invariant == "walls":
        np.testing.assert_array_equal(np.asarray(us[0])[-1, :], 0.0)
        np.testing.assert_array_equal(np.asarray(us[1])[:, -1], 0.0)
    else:
        cH, cg = swe_coeffs(cfg.dt, cfg.spacing, cfg.H0, cfg.g)
        for _ in range(n):
            us = tuple(u + cg[a] * Mus[a] * (jnp.roll(h, -1, a) - h)
                       for a, u in enumerate(us))
            h = h + sum(cH[a] * (u - jnp.roll(u, 1, a))
                        for a, u in enumerate(us))
        np.testing.assert_allclose(np.asarray(h), np.asarray(h0),
                                   rtol=1e-11, atol=1e-13)
        for u, u_start in zip(us, (u0, v0)):
            np.testing.assert_allclose(np.asarray(u), np.asarray(u_start),
                                       atol=1e-12)


# ---- the wall code: the one-chip perf step honours the masks it is handed


@pytest.mark.parametrize("mu,mv", [(0, 0), (1, 0), (0, 1), (1, 1)])
def test_wall_code_round_trips(mu, mv):
    """pack_walls then unpack_walls gives back each of the four (Mu, Mv)
    combinations, from a one-byte code."""
    from rocm_mpi_tpu.ops.swe_kernels import pack_walls, unpack_walls

    Mu = jnp.full((5, 7), float(mu), jnp.float32).at[2, 3].set(1.0 - mu)
    Mv = jnp.full((5, 7), float(mv), jnp.float32).at[2, 3].set(1.0 - mv)
    walls = pack_walls((Mu, Mv))
    assert walls.dtype.itemsize == 1
    assert int(walls[0, 0]) == mu + 2 * mv
    for got, want in zip(unpack_walls(walls, 2, jnp.float32), (Mu, Mv)):
        assert got.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _walls(kind, shape):
    """Face masks: all ones (every high wall open), or the closed basin
    with an extra interior wall row in Mu and column in Mv."""
    Mu, Mv = np.ones(shape), np.ones(shape)
    if kind == "interior":
        Mu[-1, :] = Mu[shape[0] // 3, :] = 0.0
        Mv[:, -1] = Mv[:, shape[1] // 2] = 0.0
    return Mu, Mv


def _rolled_zero_ghost(h, us, Mus, cH, cg, n):
    """`n` steps of rolled_swe_step on the state padded each step by a
    ring of zeros (masks 0 on the ring), cropped back: the zero-ghost
    convention at every domain edge, whatever the masks hold."""
    from rocm_mpi_tpu.ops.swe_kernels import rolled_swe_step

    core = (slice(1, -1),) * h.ndim
    Mps = tuple(jnp.pad(M, 1) for M in Mus)
    for _ in range(n):
        hp, ups = rolled_swe_step(jnp.pad(h, 1),
                                  tuple(jnp.pad(u, 1) for u in us), Mps,
                                  cH, cg)
        h, us = hp[core], tuple(u[core] for u in ups)
    return h, us


@pytest.mark.parametrize("case", ["several", "partial"])
@pytest.mark.parametrize("kind", ["open", "interior"])
def test_swe_one_chip_perf_honours_the_masks(monkeypatch, case, kind):
    """The one-chip perf step (its `prepare` packs the masks, the striped
    kernel reads the code) equals rolled_swe_step given the same float
    masks, with every wall open and with an interior wall, over 15
    steps (f64, rtol 1e-12, as the numpy-oracle test)."""
    shape, tm = STRIPES[case]
    _stripes(monkeypatch, tm)
    n = 15
    cfg = _cfg(shape=shape, nt=n, warmup=0)
    cH, cg = swe_coeffs(cfg.dt, cfg.spacing, cfg.H0, cfg.g)
    rng = np.random.default_rng(13)
    Mus = tuple(jnp.asarray(M) for M in _walls(kind, shape))
    h0 = jnp.asarray(1.0 + rng.uniform(size=shape))
    us0 = tuple(M * jnp.asarray(rng.uniform(-0.5, 0.5, size=shape))
                for M in Mus)
    model = ShallowWater(cfg, devices=jax.devices()[:1])
    h, us = model.advance_fn("perf")(jnp.copy(h0),
                                     tuple(jnp.copy(u) for u in us0),
                                     Mus, n)
    ref_h, ref_us = _rolled_zero_ghost(h0, us0, Mus, cH, cg, n)
    np.testing.assert_allclose(np.asarray(h), np.asarray(ref_h),
                               rtol=1e-12)
    for got, want, M in zip(us, ref_us, Mus):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-12, atol=1e-15)
        np.testing.assert_array_equal(np.asarray(got)[np.asarray(M) == 0],
                                      0.0)

"""Shallow water's 2D sharded `hide` step: `masked_swe_step` over the
whole unpadded shard as the overlap splice's interior, and the boundary
slabs as the `swe_slab_step` kernel on the windows `ghosted_window`
assembles, both reading the wall code the step's `prepare` packs. On
2x2, 1x4 and 4x1 meshes it is compared with the benchmark's plain
reference and with `rolled_swe_step` on seeded random states, with the
closed basin's masks, an interior wall and every wall open."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rocm_mpi_tpu.models.swe import SWEConfig, ShallowWater
from rocm_mpi_tpu.ops import swe_kernels
from rocm_mpi_tpu.ops.swe_kernels import swe_coeffs

MESHES = [(2, 2), (1, 4), (4, 1)]
MASKS = ["closed", "interior", "open"]
# Local shard shape and the kernels' stripes: the reference's b_width
# (32, 4) on a 72x40 shard (interior stripes 32, 32 and a partial 8;
# each row slab one 32-row stripe; column slabs of 8 rows); a 41x36
# shard, where effective_b_width clamps the row slabs to 20 and leaves a
# one-row interior; a 136x40 shard whose 72-row column slabs take three
# 32-row slab stripes, the last partial.
SHARDS = {
    "reference": ((72, 40), 32, 1024),
    "clamped": ((41, 36), 16, 1024),
    "tall": ((136, 40), 32, 32),
}


def _model(case, dims, dtype, monkeypatch, n=8):
    (l0, l1), tm, slab_rows = SHARDS[case]
    monkeypatch.setattr(swe_kernels, "_swe_stripe_height",
                        lambda n0, *row_bytes: tm)
    monkeypatch.setattr(swe_kernels, "_SWE_SLAB_ROWS", slab_rows)
    shape = (dims[0] * l0, dims[1] * l1)
    cfg = SWEConfig(global_shape=shape, lengths=(10.0, 10.0), nt=n,
                    warmup=0, dtype=dtype, dims=dims, b_width=(32, 4))
    return ShallowWater(cfg, devices=jax.devices()[:dims[0] * dims[1]])


def _masks(kind, shape):
    """(Mu, Mv): the closed basin (0 on each axis's high wall face), the
    closed basin with an interior wall row in Mu and column in Mv, or
    all ones (every wall open, as the `walls_open` fault hands them)."""
    Mu, Mv = np.ones(shape), np.ones(shape)
    if kind != "open":
        Mu[-1, :] = 0.0
        Mv[:, -1] = 0.0
    if kind == "interior":
        Mu[shape[0] // 3, :] = 0.0
        Mv[:, shape[1] // 2] = 0.0
    return Mu, Mv


def _state(kind, shape, seed):
    """(h, (u, v)), (Mu, Mv) as numpy f64: h about 1 plus noise, u and v
    noise with the wall faces 0."""
    rng = np.random.default_rng(seed)
    Mu, Mv = _masks(kind, shape)
    h = 1.0 + rng.uniform(size=shape)
    u = Mu * rng.uniform(-0.5, 0.5, size=shape)
    v = Mv * rng.uniform(-0.5, 0.5, size=shape)
    return (h, (u, v)), (Mu, Mv)


def _hide(model, state, masks, n):
    """`n` steps of the hide variant from numpy `state`, as numpy."""
    put = lambda a: jax.device_put(  # noqa: E731
        jnp.asarray(a, model.config.jax_dtype), model.grid.sharding)
    h, us = model.advance_fn("hide")(put(state[0]),
                                     tuple(put(u) for u in state[1]),
                                     tuple(put(M) for M in masks), n)
    return np.asarray(h), tuple(np.asarray(u) for u in us)


def _rolled_zero_ghost(h, us, Mus, cH, cg, n):
    """`n` steps of rolled_swe_step on the state padded each step by a
    ring of zeros (masks 0 on the ring), cropped back: the zero-ghost
    convention at every domain edge, whatever the masks hold."""
    from rocm_mpi_tpu.ops.swe_kernels import rolled_swe_step

    core = (slice(1, -1),) * 2
    Mps = tuple(jnp.pad(jnp.asarray(M), 1) for M in Mus)
    h, us = jnp.asarray(h), tuple(jnp.asarray(u) for u in us)
    for _ in range(n):
        hp, ups = rolled_swe_step(jnp.pad(h, 1),
                                  tuple(jnp.pad(u, 1) for u in us), Mps,
                                  cH, cg)
        h, us = hp[core], tuple(u[core] for u in ups)
    return np.asarray(h), tuple(np.asarray(u) for u in us)


@pytest.mark.parametrize("kind", ["closed", "interior"])
@pytest.mark.parametrize("dims", MESHES, ids=lambda d: f"{d[0]}x{d[1]}")
def test_swe_hide_matches_plain_reference(monkeypatch, dims, kind):
    """f32, against the benchmark's plain reference (jnp, its own
    operation order) over 12 steps, with the masks as handed. Tolerance
    as the one-chip kernel's: 4 roundings of the largest field a step,
    4·n·eps32·max|h|. The reference reads h' past the domain as 0, the
    program as the zero-ghost cell's update, so the two agree where the
    high faces are walls; open walls are compared with rolled_swe_step
    below."""
    from benchmark.references import swe2d as reference

    n = 12
    model = _model("reference", dims, "f32", monkeypatch, n)
    cfg = model.config
    state, masks = _state(kind, cfg.global_shape, 3)
    got_h, got_us = _hide(model, state, masks, n)
    config = {"global_shape": list(cfg.global_shape),
              "lengths": list(cfg.lengths), "H0": cfg.H0, "g": cfg.g,
              "cfl": cfg.cfl}
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    rh, rus = reference.advance_fn(config, n, jnp.float32)(
        (f32(state[0]), tuple(f32(u) for u in state[1])),
        tuple(f32(M) for M in masks))
    tol = 4 * n * float(np.finfo(np.float32).eps) * float(np.max(state[0]))
    for got, want in zip((got_h, *got_us), (rh, *rus)):
        assert float(np.max(np.abs(got - np.asarray(want)))) <= tol


@pytest.mark.parametrize("case", sorted(SHARDS))
@pytest.mark.parametrize("kind", MASKS)
@pytest.mark.parametrize("dims", MESHES, ids=lambda d: f"{d[0]}x{d[1]}")
def test_swe_hide_matches_rolled_step(monkeypatch, dims, kind, case):
    """f64 in interpret mode, against rolled_swe_step with zero ghosts
    at the domain's edge over 10 steps, to rounding (rtol 1e-12, as the
    one-chip kernel's test), on every shard geometry."""
    n = 10
    model = _model(case, dims, "f64", monkeypatch, n)
    cfg = model.config
    state, masks = _state(kind, cfg.global_shape, 5)
    got_h, got_us = _hide(model, state, masks, n)
    cH, cg = swe_coeffs(cfg.dt, cfg.spacing, cfg.H0, cfg.g)
    ref_h, ref_us = _rolled_zero_ghost(state[0], state[1], masks, cH, cg, n)
    np.testing.assert_allclose(got_h, ref_h, rtol=1e-12, atol=1e-15)
    for got, want in zip(got_us, ref_us):
        np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-15)


@pytest.mark.parametrize("kind", ["closed", "interior"])
@pytest.mark.parametrize("dims", MESHES, ids=lambda d: f"{d[0]}x{d[1]}")
def test_swe_hide_keeps_walls_and_mass(monkeypatch, dims, kind):
    """f64 over 30 steps: every wall face, the global ones and an
    interior wall, stays exactly 0, and sum(h) is invariant (the walls
    close the basin, so the divergence telescopes to 0)."""
    n = 30
    model = _model("reference", dims, "f64", monkeypatch, n)
    state, masks = _state(kind, model.config.global_shape, 7)
    h, us = _hide(model, state, masks, n)
    for u, M in zip(us, masks):
        np.testing.assert_array_equal(u[M == 0], 0.0)
    mass0 = float(np.sum(state[0]))
    assert abs(float(np.sum(h)) - mass0) <= 1e-13 * abs(mass0)


def test_swe_hide_runs_the_two_kernels_and_no_padded_step(monkeypatch):
    """One hide step is one `masked_swe_step` on the whole shard and four
    `swe_slab_step`s, with no call of the padded forms."""
    def refused(*args, **kwargs):
        raise AssertionError("a region of the 2D hide step took the "
                             "padded form")

    monkeypatch.setattr(swe_kernels, "swe_step_padded", refused)
    monkeypatch.setattr(swe_kernels, "swe_step_padded_pallas", refused)
    model = _model("reference", (2, 2), "f32", monkeypatch)
    h, us = model.init_state()
    text = str(jax.make_jaxpr(model.advance_fn("hide"))(
        h, us, model.face_masks(), 4))
    names = re.findall(r"\bname=(\w+)", text)
    assert text.count("pallas_call") == 5
    assert sorted(n for n in names if "swe" in n) == (
        ["masked_swe_step"] + ["swe_slab_step"] * 4)


def test_swe_hide_annotates_whole_shard_interior(monkeypatch):
    """The overlap step records its interior as one whole-shard call."""
    from rocm_mpi_tpu.telemetry import events

    monkeypatch.setattr(events, "_ENABLED", True)
    monkeypatch.setattr(events, "_DIR", None)
    events.clear()
    model = _model("reference", (2, 2), "f32", monkeypatch)
    h, us = model.init_state()
    model.advance_fn("hide").lower(h, us, model.face_masks(), 1)
    recs = events.records(kind="trace", name="overlap.step")
    events.clear()
    assert [r["attrs"]["interior"] for r in recs] == ["whole_shard"]
    assert recs[0]["attrs"]["leaves"] == 3

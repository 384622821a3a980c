"""Overlap (perf_hide) correctness: the variant-(3) semantics the reference
never shipped must agree with every other rung (SURVEY.md §3.4, §4b)."""


import numpy as np
import pytest

from rocm_mpi_tpu.config import DiffusionConfig
from rocm_mpi_tpu.models import HeatDiffusion
from rocm_mpi_tpu.parallel.overlap import effective_b_width


def _compare(cfg, ref_variant="ap", rtol=1e-13):
    model = HeatDiffusion(cfg)
    res_h = model.run(variant="hide")
    res_r = model.run(variant=ref_variant)
    np.testing.assert_allclose(
        np.asarray(res_h.T), np.asarray(res_r.T), rtol=rtol, atol=1e-15
    )


def test_hide_matches_ap_f64_mesh():
    _compare(
        DiffusionConfig(
            global_shape=(64, 64), nt=40, warmup=0, dims=(4, 2), b_width=(4, 4)
        )
    )


def test_hide_matches_ap_f32_pallas_strips():
    cfg = DiffusionConfig(
        global_shape=(64, 64), nt=30, warmup=0, dims=(2, 2),
        b_width=(8, 8), dtype="f32",
    )
    model = HeatDiffusion(cfg)
    res_h = model.run(variant="hide")
    res_p = model.run(variant="perf")
    np.testing.assert_allclose(
        np.asarray(res_h.T), np.asarray(res_p.T), rtol=1e-6, atol=1e-7
    )


def _spy_masked_step(monkeypatch):
    """Record the shape of every `masked_step` call the hide step
    makes: the whole-shard interior is one call on the unpadded
    shard."""
    import rocm_mpi_tpu.ops.pallas_kernels as pk

    shapes, orig = [], pk.masked_step
    monkeypatch.setattr(
        pk, "masked_step",
        lambda T, *a, **k: (shapes.append(T.shape), orig(T, *a, **k))[1],
    )
    return shapes


def _f64_ap(cfg, T=None):
    """The f64 `ap` oracle of `cfg`: `nt` steps from its own initial
    state, or one step from `T`."""
    import dataclasses

    ref = HeatDiffusion(dataclasses.replace(cfg, dtype="f64"))
    if T is None:
        return np.asarray(ref.run(variant="ap").T)
    _, Cp = ref.init_state()
    return np.asarray(ref.step_fn("ap")(np.asarray(T, np.float64), Cp))


@pytest.mark.parametrize("shape,dims,b_width", [
    # One-cell slabs: they cover exactly the ring the whole-shard
    # sweep gets wrong where a shard face is not a global face.
    ((64, 64), (2, 2), (1, 1)),
    # The reference's frame, on shards with an interior box.
    ((160, 48), (2, 2), (32, 4)),
    ((24, 24, 24), (2, 2, 2), (4, 4, 4)),
], ids=["b1", "b32x4", "3d"])
def test_hide_whole_shard_interior_matches_f64_ap(monkeypatch, shape, dims,
                                                  b_width):
    """The f32 hide step takes its interior as one whole-shard
    `masked_step` and agrees with the f64 `ap` oracle."""
    shapes = _spy_masked_step(monkeypatch)
    cfg = DiffusionConfig(global_shape=shape, lengths=(10.0,) * len(shape),
                          nt=20, warmup=0, dims=dims, b_width=b_width,
                          dtype="f32")
    model = HeatDiffusion(cfg)
    got = np.asarray(model.run(variant="hide").T, np.float64)
    assert set(shapes) == {model.grid.local_shape}
    np.testing.assert_allclose(got, _f64_ap(cfg), rtol=2e-5, atol=1e-6)


@pytest.mark.parametrize("b_width", [(1, 1), (32, 4)], ids=["b1", "b32x4"])
def test_hide_overwrites_whole_shard_edge_values(b_width):
    """Every cell next to a shard face holds a value 10^6 times the
    field's: the whole-shard sweep's value there is wildly wrong where
    the face is not a global face, so a step that kept any of them
    would miss the f64 oracle by far more than rounding."""
    cfg = DiffusionConfig(global_shape=(160, 48), lengths=(10.0, 10.0),
                          nt=1, warmup=0, dims=(2, 2), b_width=b_width,
                          dtype="f32")
    model = HeatDiffusion(cfg)
    n0, n1 = model.grid.local_shape
    rows, cols = np.indices(cfg.global_shape)
    ring = (rows % n0 == 0) | (rows % n0 == n0 - 1) | (
        (cols % n1 == 0) | (cols % n1 == n1 - 1))
    rng = np.random.default_rng(7)
    T = rng.uniform(0.5, 1.0, cfg.global_shape)
    T[ring] *= 1e6
    T = T.astype(np.float32)
    _, Cp = model.init_state()
    got = np.asarray(model.step_fn("hide")(T, Cp), np.float64)
    np.testing.assert_allclose(got, _f64_ap(cfg, T), rtol=2e-5, atol=1e-6)


@pytest.mark.parametrize("dtype,b_width,form", [
    ("f32", (4, 4), "whole_shard"),
    ("f64", (4, 4), "sliced"),
    ("f32", (8, 8), "none"),  # the slabs tile the 16x16 shard
])
def test_overlap_step_annotates_interior_form(monkeypatch, dtype, b_width,
                                              form):
    from rocm_mpi_tpu.telemetry import events

    monkeypatch.setattr(events, "_ENABLED", True)
    monkeypatch.setattr(events, "_DIR", None)
    events.clear()
    model = HeatDiffusion(DiffusionConfig(
        global_shape=(32, 32), nt=1, warmup=0, dims=(2, 2),
        b_width=b_width, dtype=dtype))
    T, Cp = model.init_state()
    model.step_fn("hide").lower(T, Cp)
    recs = events.records(kind="trace", name="overlap.step")
    events.clear()
    assert [r["attrs"]["interior"] for r in recs] == [form]
    # The slabs take their ghosts as received slabs: what is staged a
    # step is their operands, a window one cell wider than each slab.
    (n0, n1), (b0, b1) = (16, 16), b_width
    cells = 2 * (b0 + 2) * (n1 + 2)
    if n0 > 2 * b0:
        cells += 2 * (n0 - 2 * b0 + 2) * (b1 + 2)
    itemsize = np.dtype(np.float32 if dtype == "f32" else np.float64).itemsize
    assert recs[0]["attrs"]["ghosts"] == "slabs"
    assert recs[0]["attrs"]["staged_bytes"] == cells * itemsize


def test_hide_with_reference_b_width_clamped():
    # Reference b_width=(32,4) on shards smaller than the frame: clamp.
    _compare(
        DiffusionConfig(
            global_shape=(32, 32), nt=20, warmup=0, dims=(4, 2), b_width=(32, 4)
        )
    )


def test_hide_strips_cover_whole_shard():
    # b_width == shard/2: interior is empty; strips must tile exactly.
    _compare(
        DiffusionConfig(
            global_shape=(32, 32), nt=10, warmup=0, dims=(2, 2), b_width=(8, 8)
        )
    )


def test_effective_b_width():
    assert effective_b_width((64, 64), (32, 4)) == (32, 4)
    assert effective_b_width((16, 64), (32, 4)) == (8, 4)
    assert effective_b_width((3, 3), (32, 32)) == (1, 1)


def test_hide_single_device():
    _compare(
        DiffusionConfig(
            global_shape=(48, 48), nt=25, warmup=0, dims=(1, 1), b_width=(4, 4)
        )
    )


class TestDeepHalo:
    """Deep-halo sweeps (parallel.deep_halo): k steps per width-k exchange."""

    def _model(self, shape=(64, 64), dims=(2, 2), nt=24, warmup=8):
        from rocm_mpi_tpu.config import DiffusionConfig
        from rocm_mpi_tpu.models import HeatDiffusion

        cfg = DiffusionConfig(
            global_shape=shape,
            lengths=(10.0,) * len(shape),
            nt=nt,
            warmup=warmup,
            dtype="f32",
            dims=dims,
        )
        return HeatDiffusion(cfg)

    def test_matches_per_step_path(self):
        import numpy as np

        m = self._model()
        r_deep = m.run_deep(block_steps=8)
        r_ref = m.run(variant="perf")
        np.testing.assert_allclose(
            np.asarray(r_deep.T), np.asarray(r_ref.T), rtol=2e-5, atol=1e-6
        )

    def test_k1_matches_per_step_path(self):
        import numpy as np

        m = self._model(nt=6, warmup=2)
        r_deep = m.run_deep(block_steps=1)
        r_ref = m.run(variant="perf")
        np.testing.assert_allclose(
            np.asarray(r_deep.T), np.asarray(r_ref.T), rtol=2e-5, atol=1e-6
        )

    def test_3d_mesh(self):
        import numpy as np

        m = self._model(shape=(32, 32, 16), dims=(2, 2, 2), nt=8, warmup=4)
        r_deep = m.run_deep(block_steps=4)
        r_ref = m.run(variant="perf")
        np.testing.assert_allclose(
            np.asarray(r_deep.T), np.asarray(r_ref.T), rtol=2e-5, atol=1e-6
        )

    def test_hbm_shard_branch_matches_per_step(self, monkeypatch):
        # Shards too big for the (shrunk) VMEM budget route the local
        # compute to the temporal-blocked HBM sweep (multi_step_cm_hbm);
        # the schedule must still agree with the per-step path.
        import numpy as np

        import rocm_mpi_tpu.ops.pallas_kernels as pk

        monkeypatch.setattr(pk, "_VMEM_BLOCK_BUDGET_BYTES", 1024)
        m = self._model(shape=(56, 48), dims=(2, 2), nt=8, warmup=4)
        r_deep = m.run_deep(block_steps=2)  # padded shard (32,28): 32%16==0
        r_ref = m.run(variant="perf")
        np.testing.assert_allclose(
            np.asarray(r_deep.T), np.asarray(r_ref.T), rtol=2e-5, atol=1e-6
        )

    def test_bf16_deep_sweep_matches_ap(self):
        # Storage-only bf16 (r4) through the sharded deep sweep: the
        # width-k exchange moves bf16 ghosts, the local kernel computes
        # f32 and rounds once per sweep — must track the bf16 GSPMD ap
        # path to bf16 resolution.
        import numpy as np

        import jax.numpy as jnp

        m = self._model(shape=(32, 32), dims=(2, 2), nt=8, warmup=0)
        import dataclasses

        cfg16 = dataclasses.replace(m.config, dtype="bf16")
        from rocm_mpi_tpu.models import HeatDiffusion

        m16 = HeatDiffusion(cfg16)
        r_deep = m16.run_deep(block_steps=4)
        T0, Cp = m16.init_state()
        ref = m16.advance_fn("ap")(jnp.copy(T0), Cp, 8)
        np.testing.assert_allclose(
            np.asarray(r_deep.T, dtype=np.float32),
            np.asarray(ref, dtype=np.float32),
            rtol=0.02, atol=0.02,  # bf16 resolution, not a numerics bug
        )

    def test_hbm_branch_real_budget_multi_device(self, monkeypatch):
        # VERDICT r3 #7: the HBM routing scored with the PRODUCTION budget
        # (no shrunk threshold) — a genuinely HBM-class f32 shard on a
        # multi-device mesh, spy-asserted so a silent fall-through to the
        # jnp path cannot pass.
        import numpy as np

        import rocm_mpi_tpu.ops.pallas_kernels as pk

        local = pk.hbm_class_edge()  # smallest HBM-routing f32 shard edge
        m = self._model(shape=(2 * local, local), dims=(2, 1), nt=8,
                        warmup=0)
        calls = []
        orig = pk.multi_step_cm_hbm
        monkeypatch.setattr(
            pk, "multi_step_cm_hbm",
            lambda *a, **k: (calls.append(1), orig(*a, **k))[1],
        )
        r_deep = m.run_deep(block_steps=8)
        assert calls, "deep sweep did not route to multi_step_cm_hbm"
        import jax.numpy as jnp

        T0, Cp = m.init_state()  # deterministic: same IC as run_deep's
        ref = m.advance_fn("ap")(jnp.copy(T0), Cp, 8)
        np.testing.assert_allclose(
            np.asarray(r_deep.T), np.asarray(ref), rtol=2e-5, atol=2e-6
        )

    def test_hbm_branch_shape_fallback_matches_per_step(self, monkeypatch):
        # k=3 on a (28,24) shard pads to 34 rows — not a multiple of the
        # HBM sweep's stripe height — so the deep sweep must route to the
        # any-shape jnp fallback instead of crashing, and still agree.
        import numpy as np

        import rocm_mpi_tpu.ops.pallas_kernels as pk

        monkeypatch.setattr(pk, "_VMEM_BLOCK_BUDGET_BYTES", 1024)
        m = self._model(shape=(56, 48), dims=(2, 2), nt=9, warmup=3)
        r_deep = m.run_deep(block_steps=3)
        r_ref = m.run(variant="perf")
        np.testing.assert_allclose(
            np.asarray(r_deep.T), np.asarray(r_ref.T), rtol=2e-5, atol=1e-6
        )

    def test_default_depth_selection(self):
        from rocm_mpi_tpu.models.diffusion import default_deep_depth

        # Small shard: full default, clamped by shard extent.
        assert default_deep_depth((252, 252), 4) == 32
        assert default_deep_depth((16, 16), 4) == 16
        # Mid-size shard: 672² f32 fits VMEM at k=16 but not k=32 —
        # prefer the shallower VMEM-resident sweep over the HBM route.
        assert default_deep_depth((672, 672), 4) == 16
        # Genuinely HBM-resident shard: capped at the tb sweep's bound.
        assert default_deep_depth((12288, 12288), 4) == 8

    def test_depth_exceeding_shard_raises(self):
        import pytest

        from rocm_mpi_tpu.parallel.deep_halo import make_deep_sweep

        m = self._model(shape=(16, 16), dims=(4, 2))
        with pytest.raises(ValueError, match="exceeds"):
            make_deep_sweep(m.grid, 8, 1.0, 1e-4, (0.1, 0.1))

    def test_degraded_depth_warns(self):
        import warnings

        m = self._model(nt=24, warmup=9)  # gcd(9, 15, 8) = 1
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            m.run_deep(block_steps=8)
        assert any("degraded" in str(x.message) for x in w)


def test_effective_block_steps_rejects_nonpositive():
    from rocm_mpi_tpu.models.diffusion import effective_block_steps

    with pytest.raises(ValueError, match=">= 1"):
        effective_block_steps(24, 8, 0)
    with pytest.raises(ValueError, match=">= 1"):
        effective_block_steps(24, 8, -4)


def test_hide_single_device_routes_to_whole_block_step():
    # On a 1-device mesh there is nothing to hide: the hide variant must be
    # bit-identical to perf (same whole-block step, no strip bookkeeping).
    cfg = DiffusionConfig(
        global_shape=(48, 48), nt=12, warmup=0, dims=(1, 1),
        b_width=(4, 4), dtype="f32",
    )
    model = HeatDiffusion(cfg)
    r_h = model.run(variant="hide")
    r_p = model.run(variant="perf")
    np.testing.assert_array_equal(np.asarray(r_h.T), np.asarray(r_p.T))


def test_explicit_chunk_cap_warns():
    import warnings

    import jax.numpy as jnp

    from rocm_mpi_tpu.ops.pallas_kernels import fused_multi_step

    T = jnp.zeros((512, 512), jnp.float32)  # > 256 KB: chunk capped to 16
    Cp = jnp.ones_like(T)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        fused_multi_step(T, Cp, 1.0, 1e-5, (0.1, 0.1), 64, chunk=64)
    assert any("chunk degraded" in str(x.message) for x in w)


# ---- the slab ghosts against the padded splice --------------------------


def _hide_run(kind, shape, dims, b_width, dtype, wire):
    """Three hide steps of workload `kind` from a seeded state: every
    leaf of the final state, as numpy."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(11)
    common = dict(global_shape=shape, lengths=(10.0,) * len(shape), nt=3,
                  warmup=0, dims=dims, b_width=b_width, dtype=dtype,
                  wire_mode=wire)
    if kind == "wave":
        from rocm_mpi_tpu.models.wave import AcousticWave, WaveConfig

        m = AcousticWave(WaveConfig(**common))
        state = [jnp.copy(a) for a in m.init_state()]
        out = m.advance_fn("hide")(*state, 3)
    elif kind == "swe":
        from rocm_mpi_tpu.models.swe import ShallowWater, SWEConfig

        m = ShallowWater(SWEConfig(**common))
        h, us = m.init_state()
        out = m.advance_fn("hide")(jnp.copy(h), jax.tree_util.tree_map(
            jnp.copy, us), m.face_masks(), 3)
    else:
        m = HeatDiffusion(DiffusionConfig(**common))
        T0, Cp = m.init_state()
        T = rng.uniform(0.5, 1.5, shape).astype(np.asarray(T0).dtype)
        if kind == "diffusion":
            out = m.advance_fn("hide")(jax.device_put(T, m.grid.sharding),
                                       Cp, 3)
        else:  # the lane-batched hide, lanes of unlike states
            adv, bg = m.batched_advance_fn(batch=2, variant="hide")
            lanes = np.stack([T, T[::-1] * 0.5])
            out = adv(jax.device_put(lanes, bg.sharding),
                      jax.device_put(np.asarray(Cp), bg.aux_sharding),
                      jax.device_put(np.array([3, 2], np.int32),
                                     bg.batch_sharding), 3)
    return [np.asarray(a) for a in jax.tree_util.tree_leaves(out)]


@pytest.mark.parametrize("kind,shape,dims,b_width,dtype,wire", [
    ("diffusion", (64, 48), (2, 2), (1, 1), "f32", "f32"),
    ("diffusion", (160, 48), (2, 2), (32, 4), "f32", "f32"),
    ("diffusion", (40, 40), (2, 2), (32, 4), "f32", "f32"),  # clamped
    ("diffusion", (32, 32), (2, 2), (8, 8), "f32", "f32"),  # no interior
    ("diffusion", (34, 26), (2, 2), (3, 2), "f32", "f32"),  # odd shard
    ("diffusion", (48, 32), (2, 1), (4, 4), "f32", "f32"),
    ("diffusion", (64, 48), (2, 2), (4, 4), "f64", "f32"),  # sliced
    ("diffusion", (35, 26), (1, 2), (32, 4), "f64", "f32"),
    ("diffusion", (64, 48), (2, 2), (4, 4), "f32", "bf16"),
    ("wave", (48, 40), (2, 2), (4, 4), "f32", "f32"),
    ("wave", (48, 40), (2, 1), (4, 2), "f64", "bf16"),
    ("swe", (32, 32), (2, 2), (4, 4), "f64", "f32"),
    ("swe", (40, 24), (2, 1), (32, 4), "f32", "f32"),
    ("batched", (32, 32), (2, 2), (4, 4), "f32", "f32"),
    ("batched", (16, 16), (1, 2), (2, 2), "f64", "bf16"),
], ids=["b1", "b32x4", "clamped", "none", "odd", "2x1", "f64", "f64-odd",
        "bf16", "wave", "wave-f64-bf16", "swe", "swe-2x1", "batched",
        "batched-f64-bf16"])
def test_slab_ghosts_match_padded_splice(padded_splice, kind, shape, dims,
                                         b_width, dtype, wire):
    """The hide step with ghosts handed to the slabs as received slabs
    computes the bits of the step that sliced each slab's operand out of
    a ghost-ringed copy of the shard (`padded_splice`, the oracle)."""
    got = _hide_run(kind, shape, dims, b_width, dtype, wire)
    padded_splice()
    want = _hide_run(kind, shape, dims, b_width, dtype, wire)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)

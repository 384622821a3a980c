"""Main-path kernels compiled for a described TPU v5e, no chip needed.

The CPU suite runs Pallas in interpret mode, which cannot see what the
TPU compiler refuses: tiling, scoped-VMEM overruns, fusion-pass aborts.
Each test here compiles one main-path program at its real size with
`interpret=False` against a `v5e:2x2` topology description and asserts
that the kernel is in the compiled program (`tpu_custom_call`).

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, so every xdist
worker must collect the same tests and only the one running this file
loads it. Nothing here runs; compile times are ~1-2 s each except the
temporal-blocked sweep (~8 s).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import rocm_mpi_tpu.ops.pallas_kernels as pk

N = 12288  # diffusion_2D_perf's per-rank geometry (fact=12)
SPACING = (10.0 / N, 10.0 / N)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described-chip compile is written to the persistent cache but
    # cannot be read back without the chip: keep it out of the cache.
    # x64 off as on the chip (the CPU harness turns it on for the f64
    # oracle; Mosaic refuses 64-bit index maps).
    saved = {k: getattr(jax.config, k) for k in
             ("jax_enable_compilation_cache", "jax_enable_x64")}
    for k in saved:
        jax.config.update(k, False)
    compilation_cache.reset_cache()
    yield desc
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _kernel_text(fn, *args) -> str:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    return text


def test_masked_step_12288(one_chip):
    a = _sds((N, N), one_chip)
    _kernel_text(
        lambda T, Cm: pk.masked_step(T, Cm, SPACING, interpret=False), a, a
    )


def test_fused_step_cm_striped_12288(one_chip):
    _kernel_text(
        lambda Tp, Cm: pk.fused_step_cm(Tp, Cm, SPACING, interpret=False),
        _sds((N + 2, N + 2), one_chip), _sds((N, N), one_chip),
    )


def test_multi_step_cm_hbm_12288_k8(one_chip):
    a = _sds((N, N), one_chip)
    _kernel_text(
        lambda T, Cm: pk.multi_step_cm_hbm(T, Cm, SPACING, 8,
                                           interpret=False),
        a, a,
    )


def test_fused_multi_step_252_chunk16(one_chip):
    a = _sds((252, 252), one_chip)
    sp = (10.0 / 252, 10.0 / 252)
    _kernel_text(
        lambda T, Cp: pk.fused_multi_step(T, Cp, 1.0, sp[0] ** 2 / 4.1, sp,
                                          64, chunk=16, interpret=False),
        a, a,
    )


@pytest.mark.parametrize("variant,n_local", [
    ("perf", N),
    # The hide step at both 2x2 geometries chip_smoke.py runs: the
    # compiler aborted on its slab splice, and the 12224x4 slab
    # overran scoped VMEM, before PR 21.
    ("hide", 126),
    ("hide", N),
])
def test_sharded_step_2x2(topo, monkeypatch, variant, n_local):
    from rocm_mpi_tpu.config import DiffusionConfig
    from rocm_mpi_tpu.models import HeatDiffusion

    # The model picks interpret mode from the (CPU) default backend;
    # the described chip needs the compiled kernels.
    monkeypatch.setattr(pk, "_interpret_default", lambda: False)
    n = 2 * n_local
    model = HeatDiffusion(
        DiffusionConfig(global_shape=(n, n), dtype="f32", dims=(2, 2)),
        devices=topo.devices,
    )
    step, _ = model.prepared_step_fn(variant, donate=True)
    a = _sds((n, n), model.grid.sharding)
    text = step.lower(a, a).compile().as_text()
    assert "tpu_custom_call" in text
    assert "collective-permute" in text

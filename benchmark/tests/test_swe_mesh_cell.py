"""The sharded shallow-water cell swe2d-2x2-12288-hide: its files load
through benchmark/spec.py, its three readers read a synthetic trace
right, the slab byte count matches a hand count, and on a CPU mesh of
four devices at a tiny size a sound run passes its `correct` while the
mesh fault (`ghosts_zero`: the exchange skipped, every slab reading
zero ghosts) and the bf16 control fail it."""

import json
import math
import os
import types

import pytest

from conftest import REPO, plant, run_cell, write

CELL = "swe2d-2x2-12288-hide"
METRICS = ("swe_hide_step_roofline", "swe_interior_ms_per_step",
           "swe_slab_kernel_roofline")


def reader(name):
    from benchmark import spec

    return spec._load(os.path.join(REPO, "benchmark", "metrics",
                                   name + ".py"), "metric").read


def test_cell_loads_through_spec():
    from benchmark import spec

    cell = spec.load_cell(REPO, CELL)
    assert cell.chips == 4
    assert [m.name for m in cell.per_layer] == list(METRICS)
    assert [m.name for m in cell.end_to_end] == ["gpts_per_s_chip",
                                                 "setup_s"]
    assert cell.config["dims"] == [2, 2]
    assert cell.config["b_width"] == [32, 4]
    assert cell.config["global_shape"] == [24576, 24576]
    assert cell.traffic["variant"] == "hide"
    assert cell.traffic["steps_per_call"] == cell.config["nt"] == 100
    assert cell.limits["gap_motion"]["limit"] > 0
    assert {"unchanged", "velocities_held", "walls_open", "swapped",
            "ghosts_zero"} <= set(cell.system.FAULTS)
    assert callable(cell.reference.advance_fn)


# ---- the readers on a synthetic trace ----------------------------------


def _kernel(name, region):
    return (f"%{name} = f32[8,8]{{1,0}} custom-call(f32[8,8] %a), "
            'custom_call_target="tpu_custom_call", frontend_attributes='
            '{kernel_metadata={}' + f',layer="splice",region="{region}"}}')


def _plane(chip, events):
    return types.SimpleNamespace(name=f"/device:TPU:{chip}", lines=[
        types.SimpleNamespace(name="XLA Ops", events=[
            types.SimpleNamespace(name=n, start_ns=s, duration_ns=d)
            for n, s, d in events])])


# A 24x24 grid on 2x2 chips with b_width (2, 2): 12x12 shards, row slabs
# 2x12 and column slabs 8x2.
SMALL = {"global_shape": [24, 24], "dims": [2, 2], "b_width": [2, 2]}


def _run(events, steps=10):
    from benchmark import trace
    from benchmark.drivers.calls import Window

    tr = trace.from_planes([_plane(c, events) for c in (0, 1)], [0, 1])
    tr.window = (0, 1_000_000)
    return types.SimpleNamespace(
        trace=tr, window=Window(steps_per_call=steps, starts=[0.0],
                                ends=[1.0]),
        config=SMALL, peaks={"hbm_bytes_per_s": 1e9})


def test_readers_on_synthetic_trace():
    """Each chip: the interior kernel 0-600 µs, two slab kernels of 50
    µs, a copy of 100 µs and a permute's done; 10 steps at 1 GB/s."""
    done = "%cp-done.3 = f32[8]{0} collective-permute-done(f32[8]{0} %s)"
    run = _run([(_kernel("masked_swe_step.1", "interior"), 0, 600_000),
                (_kernel("swe_slab_step.2", "slab"), 600_000, 50_000),
                (_kernel("swe_slab_step.3", "slab"), 650_000, 50_000),
                ("%copy.5 = f32[8,8]{0,1} copy(f32[8,8] %x)", 700_000,
                 100_000),
                (done, 800_000, 10_000)])
    # 6 passes of 4 B over 144 points, 10 steps: 34560 B, 34.56 µs at
    # 1 GB/s, over 800 µs of compute.
    assert reader("swe_hide_step_roofline")(run) == pytest.approx(
        100 * 34.56 / 800)
    assert reader("swe_interior_ms_per_step")(run) == pytest.approx(0.06)
    # Slabs: 3 fields x 4 B x (2 x (4*14 + 24) + 2 x (10*4 + 16)) = 3264 B
    # a step, 32.64 µs over 100 µs of slab kernels.
    assert reader("swe_slab_kernel_roofline")(run) == pytest.approx(32.64)


def test_readers_without_the_new_kernels():
    """A trace with no kernel tagged by region (the parent's jnp regions)
    gives the whole step's share and nothing else; no trace, nothing."""
    run = _run([("%fusion.1 = f32[8,8]{1,0} fusion(f32[8,8] %a)", 0,
                 500_000)])
    assert reader("swe_hide_step_roofline")(run) == pytest.approx(
        100 * 34.56 / 500)
    assert reader("swe_interior_ms_per_step")(run) is None
    assert reader("swe_slab_kernel_roofline")(run) is None
    run.trace = None
    assert all(reader(name)(run) is None for name in METRICS)


# ---- the slab byte count -------------------------------------------------


@pytest.mark.parametrize("config,want", [
    # 18x20 shards, b (4, 4): row slabs 4x20 read as 6x22 and written as
    # 4x20, column slabs 10x4 read as 12x6 and written as 10x4.
    ({"global_shape": [36, 40], "dims": [2, 2], "b_width": [4, 4]},
     12 * (2 * (6 * 22 + 4 * 20) + 2 * (12 * 6 + 10 * 4))),
    # 12x20 shards: b0 = 32 clamps to 6 and leaves no rows between the
    # row slabs, so there are no column slabs.
    ({"global_shape": [24, 40], "dims": [2, 2], "b_width": [32, 4]},
     12 * 2 * (8 * 22 + 6 * 20)),
    # The cell: 12288² shards, b (32, 4).
    ({"global_shape": [24576, 24576], "dims": [2, 2], "b_width": [32, 4]},
     12 * (2 * (34 * 12290 + 32 * 12288) + 2 * (12226 * 6 + 12224 * 4))),
], ids=["b4", "clamped", "cell"])
def test_slab_bytes_hand_count(config, want):
    from benchmark import swe_slab_counts

    assert swe_slab_counts.slab_bytes_per_step(config) == want


def test_slab_windows_are_what_the_program_stages(monkeypatch):
    """The windows the count reads (each slab with its ghost ring) are
    the slab operands the program's overlap step assembles a step."""
    import jax

    from benchmark import swe_slab_counts
    from rocm_mpi_tpu.models.swe import SWEConfig, ShallowWater
    from rocm_mpi_tpu.telemetry import events

    monkeypatch.setattr(events, "_ENABLED", True)
    monkeypatch.setattr(events, "_DIR", None)
    events.clear()
    config = {"global_shape": [36, 40], "dims": [2, 2], "b_width": [4, 4]}
    model = ShallowWater(SWEConfig(
        global_shape=tuple(config["global_shape"]), lengths=(10.0, 10.0),
        dtype="f32", dims=(2, 2), b_width=(4, 4)),
        devices=jax.devices()[:4])
    h, us = model.init_state()
    model.advance_fn("hide").lower(h, us, model.face_masks(), 1)
    (rec,) = events.records(kind="trace", name="overlap.step")
    events.clear()
    windows = sum((m + 2) * (k + 2)
                  for m, k in swe_slab_counts.slab_shapes(config))
    assert rec["attrs"]["staged_bytes"] == 3 * 4 * windows


# ---- correct on the CPU mesh ---------------------------------------------


def tiny_swe_mesh_bench(root, steps=10):
    """A benchmark rooted at `root` with one throwaway cell `tiny`: the
    real configuration at 144x80 on the 2x2 mesh (72x40 shards, the
    reference's b_width), the real traffic mix at `steps` steps a call,
    the real cell's limit."""
    def real(*parts):
        with open(os.path.join(REPO, "benchmark", *parts)) as f:
            return json.load(f)

    config = real("configs", "swe2d-f32-2x2.json")
    traffic = real("traffic", "swe-hide-calls100.json")
    limit = real("limits", CELL + ".json")["gap_motion"]["limit"]
    config.update(global_shape=[144, 80], nt=100, warmup=10)
    traffic.update(steps_per_call=steps)
    write(root, "benchmark/configs/tiny.json", config)
    write(root, "benchmark/traffic/tiny-mix.json", traffic)
    write(root, "benchmark/limits/tiny.json",
          {"gap_motion": {"limit": limit}})
    write(root, "BENCHMARK.json", {
        "command": ["python3", "benchmark/run.py"], "paths": ["benchmark"],
        "run_seconds": 1,
        "configs": [{"name": "tiny", "source": "test", "reduced": [],
                     "file": "benchmark/configs/tiny.json", "why": "test"}],
        "workloads": [{"name": "tiny", "config": "tiny",
                       "traffic": "tiny-mix", "chips": 4, "why": "t"}],
        "end_to_end": [
            {"name": "gpts_per_s_chip", "unit": "Gpts/s",
             "better": "higher", "bound": 0.05, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [],
    })


def test_sound_run_is_correct(tmp_path, capsys):
    tiny_swe_mesh_bench(tmp_path)
    result, err = run_cell(tmp_path, capsys)
    assert result["correct"] is True, err[-3000:]
    assert result["device"]["count"] == 4
    check = result["checks"]["gap_motion"]
    assert 0.0 <= check["value"] <= check["limit"]


@pytest.mark.parametrize("mode", ["ghosts_zero", "control"])
def test_mesh_fault_is_not_correct(tmp_path, capsys, monkeypatch, mode):
    tiny_swe_mesh_bench(tmp_path)
    plant(monkeypatch, mode)
    result, err = run_cell(tmp_path, capsys)
    assert result["correct"] is False, err[-3000:]
    assert result["failed"] >= 1
    check = result["checks"]["gap_motion"]
    assert math.isfinite(check["value"]) and check["value"] > check["limit"]

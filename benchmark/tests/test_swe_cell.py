"""The shallow-water cell's `correct` on the CPU at a tiny size: a sound
run passes under the real cell's limit, and the bf16 control and each
of its system's FAULTS fail, driven through the rest of a run with the
timed path broken underneath, as benchmark/readings.py plants them on
the chip:

  control          the reference computed in bf16, in the program's place
  unchanged        a call that returns its state unchanged
  velocities_held  u and v not updated
  walls_open       every face mask ones
  swapped          u and v trade places after each call
"""

import json
import math
import os

import pytest

from conftest import REPO, plant, run_cell, write

CELL = "swe2d-12288-perf"


def cell_limit() -> float:
    with open(os.path.join(REPO, "benchmark", "limits",
                           CELL + ".json")) as f:
        return json.load(f)["gap_motion"]["limit"]


def tiny_swe_bench(root, steps=10):
    """A benchmark rooted at `root` with one throwaway cell `tiny`: the
    real configuration at 64x64 (one stripe of the kernel;
    tests/test_swe.py covers several), the real traffic mix at `steps`
    steps a call, the real cell's limit."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "swe2d-f32-1chip.json")) as f:
        config = json.load(f)
    with open(os.path.join(REPO, "benchmark", "traffic",
                           "swe-perf-calls100.json")) as f:
        traffic = json.load(f)
    config.update(global_shape=[64, 64], nt=100, warmup=10)
    traffic.update(steps_per_call=steps)
    write(root, "benchmark/configs/tiny.json", config)
    write(root, "benchmark/traffic/tiny-mix.json", traffic)
    write(root, "benchmark/limits/tiny.json",
          {"gap_motion": {"limit": cell_limit()}})
    write(root, "BENCHMARK.json", {
        "command": ["python3", "benchmark/run.py"], "paths": ["benchmark"],
        "run_seconds": 1,
        "configs": [{"name": "tiny", "source": "test", "reduced": [],
                     "file": "benchmark/configs/tiny.json", "why": "test"}],
        "workloads": [{"name": "tiny", "config": "tiny",
                       "traffic": "tiny-mix", "chips": 1, "why": "t"}],
        "end_to_end": [
            {"name": "gpts_per_s_chip", "unit": "Gpts/s",
             "better": "higher", "bound": 0.05, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [],
    })


def test_sound_run_is_correct(tmp_path, capsys):
    tiny_swe_bench(tmp_path)
    result, err = run_cell(tmp_path, capsys)
    assert result["correct"] is True, err[-3000:]
    assert result["attempted"] >= 2
    check = result["checks"]["gap_motion"]
    assert 0.0 <= check["value"] <= check["limit"]


@pytest.mark.parametrize("mode", ["control", "unchanged", "velocities_held",
                                  "walls_open", "swapped"])
def test_fault_is_not_correct(tmp_path, capsys, monkeypatch, mode):
    tiny_swe_bench(tmp_path)
    plant(monkeypatch, mode)
    result, err = run_cell(tmp_path, capsys)
    assert result["correct"] is False, err[-3000:]
    assert result["failed"] >= 1
    check = result["checks"]["gap_motion"]
    assert math.isfinite(check["value"]) and check["value"] > check["limit"]


def test_window_keeps_at_most_three_states(tmp_path, monkeypatch):
    """The window copies the state before calls j and j + 1 and, near its
    close, before each call, dropping the copy it took before: at most
    three copies live at once. The check compares call j and, where the
    close's copy came before the last call (call times on a loaded CPU
    vary too much to be sure of it), the last call."""
    import gc
    import weakref

    import jax

    from benchmark import spec

    tiny_swe_bench(tmp_path)
    cell = spec.load_cell(str(tmp_path), "tiny")
    prog = cell.system.build(cell.config, cell.traffic, jax.devices()[:1])
    driver = cell.driver  # drivers/calls_fields.py, loaded by name
    real = driver._copy()
    copies, most = [], [0]

    def counted(state):
        gc.collect()
        out = real(state)
        copies.append(weakref.ref(jax.tree.leaves(out)[0]))
        most[0] = max(most[0], sum(r() is not None for r in copies))
        return out

    session = driver.start(cell, 7, prog, cell.reference)
    monkeypatch.setattr(driver, "_copy", lambda: counted)
    win = session.window(1.0)
    assert win.calls >= 8
    assert len(copies) >= 2 and most[0] <= 3
    j = driver.compared_call(7)
    compared = [k for k, *_ in session.pairs]
    assert compared in ([j], [j, win.calls - 1])
    items = session.check()
    assert [label for label, _ in items] == [f"call {k}" for k in compared]
    assert session.pairs == []

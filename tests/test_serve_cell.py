"""The serving cell `serve-diff2d-1024-poisson` (BENCHMARK.json) on the
CPU at a tiny size: the service's results against the plain reference,
the faults and the control that `correct` must catch, the cell's files
found by name and run end to end by the harness, the service's lane-step
counters, the lane step's device-side tag, and the driver's inputs.

The tiny cell is a throwaway benchmark rooted in a temporary directory:
a 64² field and requests of nt 33..64, either a backlog of 8 with
arrivals at 16 a second or a backlog of 16 alone; its system, reference,
driver and readers are the repository's.
"""

from __future__ import annotations

import hashlib
import json
import math
import os
import pathlib
import random
import sys

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import spec  # noqa: E402
from benchmark.drivers import poisson  # noqa: E402
from benchmark.references import diffusion2d_serve as reference  # noqa: E402

CELL = "serve-diff2d-1024-poisson"
CONFIG = {"system": "diffusion2d_serve", "reference": "diffusion2d_serve",
          "global_shape": [64, 64], "dims": [1, 1], "lengths": [10.0, 10.0],
          "lam": 1.0, "cp0": 1.0, "dtype": "f32"}
TRAFFIC = {"driver": "poisson", "rate_per_s": 16.0, "backlog": 8,
           "nt": [33, 64], "nt_seed": 3, "ic_scale": [0.5, 1.5]}
# 16 requests queued at the window's start and no arrival within it: two
# full batches of 8 whatever the machine's speed, so a fault's reading
# does not hang on which lanes shared a batch.
BACKLOG = dict(TRAFFIC, rate_per_s=1e-3, backlog=16)
# gap_motion, max|out - ref| / max|ref - in| of the worst tile: the
# program's f32 stencil and the reference's differ in the order of their
# roundings only, which at 64² over at most 64 steps reads under 1e-6
# (3.3e-7 measured); the same reference computed in bf16 reads 3.3e-2 or
# more here, a lane whose held edges let go 5.7e-4, and a lane that did
# not move 1. The tolerance sits between, with room each side.
TOL = 3e-5


def tree_digest(path):
    h = hashlib.sha256()
    for d, dirs, files in sorted(os.walk(path)):
        dirs[:] = sorted(x for x in dirs if x != "__pycache__")
        for f in sorted(files):
            with open(os.path.join(d, f), "rb") as fh:
                h.update(f.encode() + fh.read())
    return h.hexdigest()


def _write(root, rel, obj):
    path = pathlib.Path(root) / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(obj if isinstance(obj, str) else json.dumps(obj))


def tiny_root(root, traffic=TRAFFIC):
    """A benchmark rooted at `root` whose one cell `tiny` is the serving
    cell at 64², with the serving cell's per-layer readers."""
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    serve = [m for m in bench["per_layer"]
             if CELL in m.get("workloads", ())]
    bench["configs"] = [{"name": "tiny", "source": "test", "reduced": [],
                         "file": "benchmark/configs/tiny.json", "why": "t"}]
    bench["workloads"] = [{"name": "tiny", "config": "tiny",
                           "traffic": "tiny-mix", "chips": 1, "why": "t"}]
    bench["per_layer"] = [dict(m, workloads=["tiny"]) for m in serve]
    _write(root, "BENCHMARK.json", bench)
    _write(root, "benchmark/configs/tiny.json", CONFIG)
    _write(root, "benchmark/traffic/tiny-mix.json", traffic)
    _write(root, "benchmark/limits/tiny.json", {"gap_motion": {"limit": TOL}})


@pytest.fixture
def clean(monkeypatch):
    """Keep a harness run from pointing this process's compile cache
    into a temporary directory, and reset the compile accounting it
    marks steady."""
    from rocm_mpi_tpu.telemetry import compiles

    monkeypatch.setenv("RMT_CPU_CACHE", "0")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       os.environ.get("JAX_COMPILATION_CACHE_DIR", ""))
    yield
    compiles.reset()


def run_tiny(root, capsys, seed=2**33 + 5, seconds=0.5):
    from benchmark import run

    rc = run.main(["--workload", "tiny", "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", "0"], root=str(root),
                  require_chip=False)
    assert rc == 0
    out = capsys.readouterr()
    return json.loads(out.out.strip().splitlines()[-1]), out.err


def plant(monkeypatch, mode):
    """Every cell loaded from now on runs `mode` in the program's place,
    as benchmark/readings.py plants it on the chip."""
    import types

    real = spec.load_cell

    def load_cell(root, workload):
        cell = real(root, workload)
        system = cell.system

        def broken(config, traffic, devices):
            prog = system.build(config, traffic, devices)
            if mode == "control":
                return cell.driver.control(cell, prog, cell.reference)
            return system.FAULTS[mode](prog, config, traffic,
                                       monkeypatch.setattr)

        cell.system = types.SimpleNamespace(build=broken)
        return cell

    monkeypatch.setattr(spec, "load_cell", load_cell)


def _gap_motion(cfg, req, out):
    tin = reference.initial_field(cfg, req.ic_scale, np.float32)
    ref = reference.advance(cfg, tin, req.nt, np.float32)
    worst, finite = poisson._tiled()(tin, np.asarray(out), ref)
    assert bool(finite)
    return float(worst)


def test_every_served_result_agrees_with_the_reference(clean):
    """16 requests of seeded nt and ic_scale through the system's service:
    each ticket's field agrees with the reference's nt steps from its own
    initial field, and the reference one precision down would not."""
    import jax.numpy as jnp

    cell = spec.load_cell(str(REPO), CELL)
    prog = cell.system.build(CONFIG, TRAFFIC, None)
    rng = random.Random(7)
    reqs = [prog.request(f"r{i}", rng.randint(33, 64), rng.uniform(0.5, 1.5))
            for i in range(16)]
    svc = prog.new_service()
    tickets = [svc.queue.submit(r) for r in reqs]
    report = svc._drain_all()
    assert report.served == 16
    for t in tickets:
        assert _gap_motion(CONFIG, t.request, t.result(timeout=0)[0]) < TOL
    r = reqs[0]
    low = reference.initial_field(CONFIG, r.ic_scale, jnp.bfloat16)
    for _ in range(r.nt):
        low = reference.advance(CONFIG, low, 1, jnp.bfloat16)
    assert _gap_motion(CONFIG, r, np.asarray(low)) > 10 * TOL


def test_sound_cell_is_correct(tmp_path, capsys, clean):
    tiny_root(tmp_path)
    result, err = run_tiny(tmp_path, capsys)
    assert result["correct"] is True, err[-3000:]
    assert result["failed"] == 0 and result["attempted"] >= 8
    assert set(result["metrics"]) == {"gpts_per_s_chip", "setup_s"}
    assert "0 backend compiles inside the window" in err
    assert 0.0 <= result["checks"]["gap_motion"]["value"] < TOL


@pytest.mark.parametrize("mode", ["unchanged", "overrun", "swapped",
                                  "ring_frozen", "edges_free", "control"])
def test_fault_is_not_correct(tmp_path, capsys, monkeypatch, clean, mode):
    tiny_root(tmp_path, BACKLOG)
    plant(monkeypatch, mode)
    result, err = run_tiny(tmp_path, capsys)
    assert result["correct"] is False, err[-3000:]
    assert result["failed"] >= 1
    check = result["checks"]["gap_motion"]
    assert math.isfinite(check["value"]) and check["value"] > 10 * TOL


def test_cell_loads_by_name_and_needs_no_edit(tmp_path, capsys, clean):
    """The cell's files are found by name, each reader among them; the
    tiny twin runs end to end through the harness without editing a
    file of the repository's benchmark."""
    before = tree_digest(REPO / "benchmark")
    cell = spec.load_cell(str(REPO), CELL)
    assert cell.chips == 1 and cell.traffic["driver"] == "poisson"
    assert {m.name for m in cell.per_layer} == {
        "serve_step_roofline", "serve_padded_lane_steps_pct",
        "serve_host_ms_per_batch", "device_idle_pct"}
    assert set(cell.system.FAULTS) == {"unchanged", "overrun", "swapped",
                                       "ring_frozen", "edges_free"}
    tiny_root(tmp_path)
    result, _ = run_tiny(tmp_path, capsys, seed=11)
    assert result["correct"] is True
    assert tree_digest(REPO / "benchmark") == before


def test_window_feeds_the_serving_readers(clean):
    """A window's counters reach the per-layer readers: padding is the
    executed lane-steps that served no request, host time excludes the
    fetch's wait on the device; a parent's service, with no counters,
    gives the readers nothing to read."""
    import types

    real = spec.load_cell(str(REPO), CELL)
    cell = types.SimpleNamespace(config=CONFIG, traffic=TRAFFIC)
    prog = real.system.build(CONFIG, TRAFFIC, None)
    session = poisson.start(cell, 5, prog, reference)
    win = session.window(0.3)
    session.check()
    assert win.requests >= 8 and win.compiles == 0
    assert win.pipeline["lane_steps_useful"] == win.steps
    assert win.pipeline["lane_steps_executed"] >= win.steps
    run = types.SimpleNamespace(window=win, trace=None, config=CONFIG)
    readers = {m.name: m.reader for m in real.per_layer}
    pad = readers["serve_padded_lane_steps_pct"].read(run)
    assert pad == pytest.approx(
        100 * (1 - win.steps / win.pipeline["lane_steps_executed"]))
    p = win.pipeline
    stages = sum(p[k] for k in ("assemble_s", "dispatch_s", "fetch_s",
                                "resolve_s"))
    host = readers["serve_host_ms_per_batch"].read(run)
    assert 0.0 < host <= 1e3 * stages / p["batches"]
    assert readers["serve_step_roofline"].read(run) is None  # no trace
    bare = types.SimpleNamespace(window=types.SimpleNamespace(
        pipeline={"batches": 3, "assemble_s": 0.1, "dispatch_s": 0.1,
                  "fetch_s": 0.1, "resolve_s": 0.1}), trace=None,
        config=CONFIG)
    for r in readers.values():
        assert r.read(bare) is None


@pytest.mark.parametrize("nts,width", [([40] * 4, 4), ([40, 33, 64], 4)])
def test_lane_step_counters_add_up(clean, nts, width):
    """useful <= executed; equal for a full batch of equal nt, and a
    short lane's frozen tail and an idle lane count as executed only."""
    from rocm_mpi_tpu.serving.queue import Request
    from rocm_mpi_tpu.serving.service import ServeConfig, SimulationService

    svc = SimulationService(config=ServeConfig(max_width=width))
    svc.run_trace([Request(request_id=f"c{i}", global_shape=(16, 16),
                           dtype="f32", nt=n) for i, n in enumerate(nts)])
    p = svc.pipeline_stats()
    assert p["lane_steps_useful"] == sum(nts)
    assert p["lane_steps_executed"] == width * max(nts)
    assert p["lane_steps_useful"] <= p["lane_steps_executed"]
    assert 0.0 <= p["fetch_wait_s"] <= p["fetch_s"]


def test_batched_shard_step_tags_its_lane_step():
    """The batched shard step's stencil carries layer="lane_step", its
    exchange layer="exchange" and its loop layer="driver": the trace can
    tell the serve step's device time from its loop."""
    import re

    import jax
    import jax.numpy as jnp

    from rocm_mpi_tpu.config import DiffusionConfig
    from rocm_mpi_tpu.models import HeatDiffusion

    m = HeatDiffusion(DiffusionConfig(global_shape=(32, 32), dtype="f32",
                                      dims=(2, 2)),
                      devices=jax.devices()[:4])
    adv, bg = m.batched_advance_fn(batch=2, variant="shard")
    tb = jax.ShapeDtypeStruct((2, 32, 32), jnp.float32, sharding=bg.sharding)
    cp = jax.ShapeDtypeStruct((32, 32), jnp.float32,
                              sharding=bg.aux_sharding)
    steps = jax.ShapeDtypeStruct((2,), jnp.int32, sharding=bg.batch_sharding)
    text = adv.lower(tb, cp, steps, 8).as_text()
    assert {"lane_step", "exchange", "driver"} <= set(
        re.findall(r'layer = "(\w+)"', text))
    for op in ("multiply", "divide", "select"):
        assert any(f"stablehlo.{op}" in ln and 'layer = "lane_step"' in ln
                   for ln in text.splitlines()), op


def test_requests_are_seeded():
    """The same seed gives the same requests: arrivals and ic_scale follow
    the run's seed, nt its own i.i.d. stream, the traffic's nt_seed, so
    that every seed serves the same steps."""
    t = dict(TRAFFIC, nt=[513, 1024], backlog=3)
    a = poisson.requests(2**40 + 3, t, 1100)
    assert a == poisson.requests(2**40 + 3, t, 1100)
    b = poisson.requests(2**40 + 4, t, 1100)
    assert [x[1] for x in a] == [x[1] for x in b]
    assert [x[0] for x in a] != [x[0] for x in b]
    assert [x[2] for x in a] != [x[2] for x in b]
    nts = [nt for _, nt, _ in a]
    assert 513 <= min(nts) < 520 and 1017 < max(nts) <= 1024
    assert len(set(nts)) > 400  # i.i.d., not a sweep of the range
    assert nts != [x[1] for x in poisson.requests(2**40 + 3,
                                                  dict(t, nt_seed=4), 1100)]
    offsets = [o for o, _, _ in a]
    assert offsets[:3] == [0.0] * 3 and offsets[3] > 0.0
    assert all(x < y for x, y in zip(offsets[3:], offsets[4:]))
    assert all(0.5 <= s <= 1.5 for _, _, s in a)


def test_window_compares_every_lane_position(clean):
    """Two full batches of 8 from a backlog: the check compares a request
    from each of the 8 lane positions, the first and the last served
    among them."""
    import types

    real = spec.load_cell(str(REPO), CELL)
    cell = types.SimpleNamespace(config=CONFIG, traffic=BACKLOG)
    prog = real.system.build(CONFIG, BACKLOG, None)
    session = poisson.start(cell, 2**35 + 9, prog, reference)
    win = session.window(0.2)
    assert win.requests == 16
    ordinals = [t.ordinal for t in session.compared]
    assert len(ordinals) >= 8 and len(set(ordinals)) == len(ordinals)
    first = min(ordinals)  # the warm-up's requests came before
    assert {(o - first) % 8 for o in ordinals} == set(range(8))
    assert max(ordinals) - first == 15
    items = session.check()
    assert len(items) == len(ordinals)
    assert all(v["gap_motion"] < TOL for _, v in items)

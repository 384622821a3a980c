"""Continuous batching (PR 19, docs/SERVING.md "Continuous batching").

Covers the step-granular lane swap (segmented batched drain: resolved
lanes swap OUT and queued same-program-class requests swap IN at
segment boundaries of ONE compiled program) and the program-
consolidation shape-padding ladder (serving/bins.ladder_shape), plus
their gates: the bin scheduler's exactly-at-floor boundary, the
ladder's split-instead-of-pad tolerance rule, BinStats' ladder-waste
vs width-padding-waste accounting, the manifest `continuous` block and
budgets-row schema gates, and the two acceptance drills —

* the bitwise pin: a lane swapped in at a segment boundary produces
  results identical to its standalone run, on all three workloads plus
  a resume-session lane, with `compiles.steady_state == 0` across the
  whole swap-heavy trace;
* the utilization win: under the heavy-tailed trace, the continuous
  drain shows strictly higher step-weighted occupancy (above the
  committed `serving.occupancy` floor) and no worse device-bubble than
  batch-synchronous at equal results, and the ladder provably reduces
  program-class count within `padded_flops_tolerance`.
"""

from __future__ import annotations

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent

from rocm_mpi_tpu.config import DiffusionConfig  # noqa: E402
from rocm_mpi_tpu.models import HeatDiffusion  # noqa: E402
from rocm_mpi_tpu.models.swe import SWEConfig, ShallowWater  # noqa: E402
from rocm_mpi_tpu.models.wave import AcousticWave, WaveConfig  # noqa: E402
from rocm_mpi_tpu.serving import bins as sbins  # noqa: E402
from rocm_mpi_tpu.serving.queue import Request  # noqa: E402
from rocm_mpi_tpu.serving.service import (  # noqa: E402
    ServeConfig,
    SimulationService,
)
from rocm_mpi_tpu.telemetry import compiles  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_compile_accounting():
    """The steady-state recompile count is process-wide: start each test
    from zero, whatever an earlier test in the same process compiled."""
    compiles.reset()


def _put(arr, sharding):
    return jax.device_put(np.asarray(arr), sharding)


# ---------------------------------------------------------------------------
# The bin scheduler's occupancy-floor boundary and the ladder rules
# ---------------------------------------------------------------------------


def test_plan_batches_exactly_at_occupancy_floor_is_kept():
    """The split rule is STRICTLY below the floor: a tail batch whose
    occupancy lands exactly ON `occupancy_floor` keeps its width — only
    dropping below it forces the narrower split."""
    # 5 lanes at width 8 = 0.625 occupancy: exactly at the floor, kept.
    assert sbins.plan_batches(5, 8, occupancy_floor=0.625) == [8]
    # One epsilon above the same ratio: 0.625 < 0.626 now splits.
    assert sbins.plan_batches(5, 8, occupancy_floor=0.626) == [4, 1]


def test_ladder_rung_values_and_quantum():
    # quantum = max(4, pow2_floor(n) // 4): 30 -> q 4 -> 32; 126 -> q 16
    assert sbins.ladder_rung(30) == 32
    assert sbins.ladder_rung(14) == 16
    assert sbins.ladder_rung(126) == 128
    assert sbins.ladder_rung(62) == 64
    assert sbins.ladder_rung(32) == 32  # already on a rung
    with pytest.raises(ValueError, match=">= 1"):
        sbins.ladder_rung(0)


def test_ladder_shape_split_instead_of_pad():
    """A rung whose padded-FLOPs inflation exceeds the tolerance must
    NOT pad — the shape keeps its exact program class (the shape
    edition of the occupancy floor's split rule)."""
    assert sbins.ladder_shape((30, 14)) == (32, 16)
    infl = sbins.ladder_inflation((30, 30), (32, 32))
    assert infl == pytest.approx(0.1378, abs=1e-3)
    assert sbins.ladder_shape((30, 30)) == (32, 32)
    # (5, 5) -> rung (8, 8) inflates 64/25 - 1 = 1.56 > 0.25: unchanged
    assert sbins.ladder_inflation((5, 5), (8, 8)) > 1.5
    assert sbins.ladder_shape((5, 5)) == (5, 5)
    # tolerance 0 admits only exact-rung shapes
    assert sbins.ladder_shape((30, 30), tolerance=0.0) == (30, 30)
    assert sbins.ladder_shape((32, 32), tolerance=0.0) == (32, 32)
    with pytest.raises(ValueError, match=">= 0"):
        sbins.ladder_shape((16, 16), tolerance=-0.1)


def test_binstats_ladder_waste_distinct_from_width_padding():
    """`ladder_waste` counts padded CELLS, `padding_waste` counts idle
    and frozen lane STEPS — a batch can carry one without the other,
    and the manifest reports them separately."""
    key = sbins.BinKey("diffusion", (32, 32), "f32", (), "shard",
                       "f32", 4)
    # Width padding only: full-width exact-shape lanes, mixed lengths.
    st = sbins.BinStats(key=key)
    st.note_batch(4, [6, 3, 6], 6)
    assert st.padding_waste == pytest.approx(1 - 15 / 24)
    assert st.ladder_waste == 0.0  # no cell accounting banked
    st.note_batch(1, [6], 6, split=True)
    assert st.splits == 1

    # Ladder padding only: every slot live every step, but each lane's
    # 30x30 domain rides the 32x32 rung program.
    st2 = sbins.BinStats(key=key)
    st2.note_continuous(2, [4, 4], 4, swaps_in=0, segments=2,
                        lane_cells=[(900, 1024), (900, 1024)])
    assert st2.padding_waste == 0.0
    assert st2.ladder_waste == pytest.approx(1 - 900 / 1024)
    assert st2.swaps_in == 0 and st2.segments == 2

    # Continuous accounting caps slot occupancy at the compiled width
    # even when swaps seat more tenants than slots.
    st3 = sbins.BinStats(key=key)
    st3.note_continuous(2, [4, 3, 4], 8, swaps_in=1, segments=4,
                        lane_cells=[(1024, 1024)] * 3)
    assert st3.live_lanes == 2 and st3.requests == 3
    assert st3.ladder_waste == 0.0
    assert st3.padding_waste == pytest.approx(1 - 11 / 16)


# ---------------------------------------------------------------------------
# The acceptance bitwise pin: swap-heavy trace, three workloads + resume
# ---------------------------------------------------------------------------


def _swap_trace(tag: str):
    """Three same-bucket groups (bucket 4) on one shape class, more
    lanes than width so every group swaps at segment boundaries."""
    mix = (
        [("diffusion", 4 if i % 2 == 0 else 3) for i in range(6)]
        + [("wave", 4 if i % 2 == 0 else 3) for i in range(4)]
        + [("swe", 4 if i % 2 == 0 else 3) for i in range(4)]
    )
    return [
        Request(request_id=f"{tag}-{wl}-{i:02d}", workload=wl,
                global_shape=(16, 16), dtype="f64", nt=nt,
                ic_scale=1.0 + 0.03 * i)
        for i, (wl, nt) in enumerate(mix)
    ]


def test_segmented_swap_bitwise_all_workloads_and_resume(tmp_path):
    """The tentpole pin: a swap-heavy trace through segments=2 width-2
    programs — every result bitwise-equal to a batch-synchronous
    width-1 twin service AND to direct standalone advance runs, a
    resume-session lane rides the same segmented group, and the whole
    trace recompiles nothing (`compiles.steady_state == 0`)."""
    compiles.install()
    sessions = tmp_path / "sessions"
    svc = SimulationService(config=ServeConfig(
        max_width=2, segments=2, sessions_dir=str(sessions),
    ))
    # Seed the session: its own bucket-2 program, compiled pre-trace.
    seed = Request(request_id="seed", workload="diffusion",
                   global_shape=(16, 16), dtype="f64", nt=2,
                   ic_scale=1.2, session="cont-sess")
    svc.queue.submit(seed)
    svc._drain_all()

    trace = _swap_trace("swap")
    resume = Request(request_id="res", workload="diffusion",
                     global_shape=(16, 16), dtype="f64", nt=4,
                     ic_scale=1.2, session="cont-sess", resume=True)
    tickets = [svc.queue.submit(r) for r in trace]
    t_res = svc.queue.submit(resume)
    report = svc._drain_all()
    assert report.served == len(trace) + 1 and report.failed == 0
    assert report.compiles["steady_state"] == 0
    assert report.continuous["segments"] == 2
    assert report.continuous["swaps_in"] >= 3  # every group re-seats
    assert t_res.start_step == 2 and t_res.steps_run == 2

    # Twin service: batch-synchronous, one lane per program.
    tw_sessions = tmp_path / "tw-sessions"
    twin = SimulationService(config=ServeConfig(
        max_width=1, sessions_dir=str(tw_sessions),
    ))
    twin.queue.submit(Request(
        request_id="seed-tw", workload="diffusion",
        global_shape=(16, 16), dtype="f64", nt=2, ic_scale=1.2,
        session="cont-sess", ))
    twin._drain_all()
    tw_tickets = [twin.queue.submit(r) for r in _swap_trace("swap")]
    tw_res = twin.queue.submit(Request(
        request_id="res-tw", workload="diffusion",
        global_shape=(16, 16), dtype="f64", nt=4, ic_scale=1.2,
        session="cont-sess", resume=True,
    ))
    twin._drain_all()
    for i, (a, b) in enumerate(zip(tickets, tw_tickets)):
        for la, lb in zip(a.result(timeout=5), b.result(timeout=5)):
            assert np.array_equal(np.asarray(la), np.asarray(lb)), i
    for la, lb in zip(t_res.result(timeout=5), tw_res.result(timeout=5)):
        assert np.array_equal(np.asarray(la), np.asarray(lb))

    # Direct standalone pins for one swapped-in lane per workload (the
    # initial roster holds the first two lanes of each group — index 5
    # in each group arrived via a segment-boundary swap) and the resume
    # lane vs one uninterrupted run.
    dlane = trace[5]
    cfg = DiffusionConfig(global_shape=(16, 16), nt=8, warmup=0,
                          dtype="f64", dims=(1, 1))
    m = HeatDiffusion(cfg, devices=jax.devices()[:1])
    T0, Cp = m.init_state()
    adv = m.advance_fn("shard")
    ref = np.asarray(adv(
        jnp.asarray(np.asarray(T0) * dlane.ic_scale), Cp, dlane.nt))
    assert np.array_equal(tickets[5].result(timeout=5)[0], ref)

    wlane = trace[9]  # 4th wave request: swapped in
    wcfg = WaveConfig(global_shape=(16, 16), nt=8, warmup=0,
                      dtype="f64", dims=(1, 1))
    w = AcousticWave(wcfg, devices=jax.devices()[:1])
    U0, _, _C2 = w.init_state()
    U0s = np.asarray(U0) * wlane.ic_scale
    rU, rUp = w.advance_fn("shard")(
        jnp.asarray(U0s), jnp.asarray(U0s.copy()), _C2, wlane.nt)
    got_w = tickets[9].result(timeout=5)
    assert np.array_equal(got_w[0], np.asarray(rU))
    assert np.array_equal(got_w[1], np.asarray(rUp))

    slane = trace[13]  # 4th swe request: swapped in
    scfg = SWEConfig(global_shape=(16, 16), nt=8, warmup=0,
                     dtype="f64", dims=(1, 1))
    s = ShallowWater(scfg, devices=jax.devices()[:1])
    h0, _ = s.init_state()
    Mus = s.face_masks()
    rh, rus = s.advance_fn("shard")(
        _put(np.asarray(h0) * slane.ic_scale, s.grid.sharding),
        tuple(_put(np.zeros(scfg.global_shape), s.grid.sharding)
              for _ in range(2)),
        Mus, slane.nt,
    )
    got_s = tickets[13].result(timeout=5)
    assert np.array_equal(got_s[0], np.asarray(rh))
    for a in range(2):
        assert np.array_equal(got_s[1 + a], np.asarray(rus[a]))

    # Resume lane vs one uninterrupted 4-step run.
    ref_res = np.asarray(adv(jnp.asarray(np.asarray(T0) * 1.2), Cp, 4))
    assert np.array_equal(t_res.result(timeout=5)[0], ref_res)


# ---------------------------------------------------------------------------
# The ladder consolidates program classes — bitwise, within tolerance
# ---------------------------------------------------------------------------


def _ladder_trace(tag: str):
    mix = [
        ("diffusion", (30, 30)), ("diffusion", (32, 32)),
        ("diffusion", (30, 30)), ("wave", (30, 30)),
        ("wave", (32, 32)), ("swe", (30, 30)),
    ]
    return [
        Request(request_id=f"{tag}-{i}", workload=wl, global_shape=sh,
                dtype="f32", nt=4 if i % 2 == 0 else 3,
                ic_scale=1.0 + 0.04 * i)
        for i, (wl, sh) in enumerate(mix)
    ]


def test_ladder_consolidates_program_classes_bitwise():
    """(30,30) and (32,32) diffusion/wave traffic merges onto the
    32x32 rung (inflation 0.138 <= padded_flops_tolerance 0.25) —
    strictly fewer program classes, every result bitwise-equal to the
    exact-shape service; SWE is ladder-ineligible and keeps its exact
    class."""
    from rocm_mpi_tpu.perf.traffic import load_budgets

    tol = load_budgets()["serving"]["padded_flops_tolerance"]
    assert sbins.ladder_inflation((30, 30), (32, 32)) <= tol
    assert sbins.bin_key(
        _ladder_trace("k")[0], ladder_tolerance=tol
    ).shape == (32, 32)

    exact = SimulationService(config=ServeConfig(max_width=2))
    e_tickets = [exact.queue.submit(r) for r in _ladder_trace("ex")]
    e_report = exact._drain_all()

    lad = SimulationService(config=ServeConfig(
        max_width=2, segments=2, ladder=True,
    ))
    l_tickets = [lad.queue.submit(r) for r in _ladder_trace("la")]
    l_report = lad._drain_all()

    assert l_report.failed == 0 and e_report.failed == 0
    # 6 exact classes (3 shapes x diffusion + 2 x wave + 1 swe by
    # steps-bucket... shapes split them) collapse: diffusion 2 -> 1,
    # wave 2 -> 1; swe keeps its exact (30, 30) class.
    assert l_report.n_bins < e_report.n_bins
    assert l_report.compiles["steady_state"] == 0
    ladder_keys = list(l_report.bins)
    assert any(k.workload == "swe" and k.shape == (30, 30)
               for k in ladder_keys)
    assert not any(k.workload in ("diffusion", "wave")
                   and k.shape == (30, 30) for k in ladder_keys)
    # Ladder cell-padding is visible in the stats, distinctly.
    assert any(st.ladder_waste > 0.0 for st in l_report.bins.values())

    for i, (a, b) in enumerate(zip(e_tickets, l_tickets)):
        for la, lb in zip(a.result(timeout=5), b.result(timeout=5)):
            assert np.array_equal(np.asarray(la), np.asarray(lb)), i


# ---------------------------------------------------------------------------
# The utilization acceptance: occupancy up, bubble no worse, results equal
# ---------------------------------------------------------------------------


HEAVY_NTS = [16, 9, 10, 9, 16, 9, 9, 10, 9, 9, 10, 9]


def _heavy_trace(tag: str):
    return [
        Request(request_id=f"{tag}-{i:02d}", workload="diffusion",
                global_shape=(16, 16), dtype="f32", nt=nt,
                ic_scale=1.0 + 0.01 * i)
        for i, nt in enumerate(HEAVY_NTS)
    ]


def test_continuous_occupancy_and_bubble_regress_gate():
    """The regress-gated utilization win, measured warmed: the
    continuous drain's step-weighted occupancy is strictly higher than
    batch-synchronous AND clears the committed `serving.occupancy`
    floor, its device-bubble is no worse, and the two drains return
    bitwise-identical results."""
    from rocm_mpi_tpu.perf.traffic import load_budgets

    floor = load_budgets()["serving"]["occupancy"]
    results = {}
    for mode, segs in (("sync", 1), ("cont", 4)):
        svc = SimulationService(config=ServeConfig(
            max_width=4, segments=segs,
        ))
        svc.run_trace(_heavy_trace(f"warm-{mode}"))  # compile it all
        tickets = [svc.queue.submit(r)
                   for r in _heavy_trace(f"meas-{mode}")]
        p0 = dict(svc._pipe)
        rep = svc._drain_all()
        d_busy = svc._pipe["busy_s"] - p0["busy_s"]
        d_wall = svc._pipe["wall_s"] - p0["wall_s"]
        assert d_wall > 0
        bubble = max(0.0, 1.0 - d_busy / d_wall)
        assert rep.compiles["steady_state"] == 0
        if segs > 1:
            occ = rep.continuous["occupancy"]
            assert rep.continuous["swaps_in"] >= 1
        else:
            # The batch-synchronous comparable: step-weighted useful
            # fraction (1 - padding_waste aggregated over the drain) —
            # NOT the slot-count occupancy, which ignores frozen tails.
            occ = sum(st.useful_steps for st in rep.bins.values()) \
                / sum(st.machine_steps for st in rep.bins.values())
        results[mode] = (
            [t.result(timeout=5) for t in tickets], occ, bubble,
        )

    out_s, occ_s, bub_s = results["sync"]
    out_c, occ_c, bub_c = results["cont"]
    for i, (a, b) in enumerate(zip(out_s, out_c)):
        for la, lb in zip(a, b):
            assert np.array_equal(np.asarray(la), np.asarray(lb)), i
    assert occ_c > occ_s, (occ_c, occ_s)
    assert occ_c >= floor, (occ_c, floor)
    # Wall-clock gauge: allow measurement noise, but the continuous
    # drain must not be structurally worse (measured ~0.14 vs ~0.33 on
    # the CPU lowering — chains keep a flight open across segments).
    assert bub_c <= bub_s + 0.05, (bub_c, bub_s)


# ---------------------------------------------------------------------------
# Schema gates: manifest `continuous` block and the budgets rows
# ---------------------------------------------------------------------------


def test_manifest_continuous_block_schema_gate(tmp_path):
    from rocm_mpi_tpu.telemetry.regress import check_schema

    svc = SimulationService(config=ServeConfig(max_width=2, segments=2))
    svc.run_trace(_swap_trace("man"))
    path = tmp_path / "serve-manifest.json"
    doc = svc.write_manifest(path)
    assert doc["continuous"]["segments"] == 2
    assert doc["continuous"]["swaps_in"] >= 1
    assert 0.0 <= doc["continuous"]["occupancy"] <= 1.0
    assert sbins.validate_manifest_doc(doc) == []
    assert check_schema([path]) == []

    bad = tmp_path / "bad-manifest.json"
    doc1 = json.loads(path.read_text())
    doc1["continuous"]["segments"] = 0
    bad.write_text(json.dumps(doc1))
    assert any("segments" in p for p in check_schema([bad]))

    doc2 = json.loads(path.read_text())
    doc2["continuous"]["occupancy"] = 1.7
    bad.write_text(json.dumps(doc2))
    assert any("occupancy" in p for p in check_schema([bad]))


def test_budgets_continuous_rows_schema_gate(tmp_path):
    from rocm_mpi_tpu.perf.traffic import load_budgets
    from rocm_mpi_tpu.telemetry.regress import check_schema

    doc = load_budgets()
    assert doc["serving"]["padded_flops_tolerance"] == 0.25
    assert 0.0 < doc["serving"]["occupancy"] <= 1.0

    bad = tmp_path / "budgets.json"
    doc["serving"]["padded_flops_tolerance"] = -1
    bad.write_text(json.dumps(doc))
    assert any("padded_flops_tolerance" in p for p in check_schema([bad]))

    doc = load_budgets()
    doc["serving"]["occupancy"] = 1.5
    bad.write_text(json.dumps(doc))
    assert any("occupancy" in p for p in check_schema([bad]))

"""Multi-tenant batched serving (docs/SERVING.md; ROADMAP item 1).

Covers the space×batch mesh layer (parallel.mesh.BatchedGrid +
exchange_halo_batched + the batched deep sweep), the per-lane bitwise
parity contract of every model's batched_advance_fn, the bin
scheduler's key/packing determinism, the service driver end to end
(program count == len(bins), compiles.steady_state == 0, session
checkpoint multiplexing, preemption requeue, queue-driven elasticity),
the batched traffic audit + its doctored over-padded fixture, the
serve-request/bin-manifest schema gate, and the monitor's SERVE badge.

The acceptance drill: a heterogeneous 50-request trace through
apps/serve.py compiles exactly len(bins) programs with
`compiles.steady_state == 0`, every request's result bitwise-equal to
its standalone single-run twin. The gloo-real 2-rank edition drives
tests/serving_worker.py via spawn_ranks.
"""

from __future__ import annotations

import json
import os
import pathlib
import signal
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent

from rocm_mpi_tpu.config import DiffusionConfig  # noqa: E402
from rocm_mpi_tpu.models import HeatDiffusion  # noqa: E402
from rocm_mpi_tpu.models.swe import SWEConfig, ShallowWater  # noqa: E402
from rocm_mpi_tpu.models.wave import AcousticWave, WaveConfig  # noqa: E402
from rocm_mpi_tpu.parallel import mesh as pmesh  # noqa: E402
from rocm_mpi_tpu.serving import bins as sbins  # noqa: E402
from rocm_mpi_tpu.serving.queue import (  # noqa: E402
    DEFAULT_RETRY_AFTER_S,
    MAX_RETRY_AFTER_S,
    RETRY_WINDOW_STALE_S,
    Request,
    RequestQueue,
    load_trace,
    request_from_record,
    request_to_record,
    validate_request_record,
)
from rocm_mpi_tpu.serving.service import (  # noqa: E402
    ServeConfig,
    SimulationService,
)
from rocm_mpi_tpu.telemetry import compiles  # noqa: E402


def _put(arr, sharding):
    return jax.device_put(np.asarray(arr), sharding)


# ---------------------------------------------------------------------------
# The space×batch mesh layer
# ---------------------------------------------------------------------------


def test_batched_grid_shapes_and_specs():
    bg = pmesh.init_batched_grid(
        6, 16, 16, space_dims=(1, 2), batch_dims=2,
        devices=jax.devices()[:4],
    )
    assert bg.axis_names == ("batch", "gx", "gy")
    assert bg.batch == 6 and bg.batch_dims == 2 and bg.local_batch == 3
    assert bg.global_shape == (6, 16, 16)
    assert bg.local_shape == (3, 16, 8)
    assert tuple(bg.spec) == ("batch", "gx", "gy")
    assert tuple(bg.aux_spec) == ("gx", "gy")
    assert bg.space.dims == (1, 2)


def test_batched_grid_validation():
    with pytest.raises(ValueError, match="not divisible"):
        pmesh.init_batched_grid(3, 16, 16, space_dims=(1, 1),
                                batch_dims=2, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="devices"):
        pmesh.init_batched_grid(4, 16, 16, space_dims=(2, 2),
                                batch_dims=4, devices=jax.devices())


def test_rebuild_batched_for_mesh_grows_rows():
    bg = pmesh.init_batched_grid(4, 16, 16, space_dims=(1, 1),
                                 batch_dims=1, devices=jax.devices()[:1])
    grown = pmesh.rebuild_batched_for_mesh(
        bg, batch_dims=2, devices=jax.devices()[:2]
    )
    assert grown.batch_dims == 2 and grown.batch == 4
    assert grown.space.global_shape == bg.space.global_shape


def test_exchange_halo_batched_rejects_stateful_wire():
    from rocm_mpi_tpu.parallel.halo import exchange_halo_batched

    bg = pmesh.init_batched_grid(2, 16, 16, space_dims=(1, 1),
                                 batch_dims=1, devices=jax.devices()[:1])
    with pytest.raises(ValueError, match="stateful"):
        exchange_halo_batched(jnp.zeros((2, 16, 16)), bg,
                              wire_mode="int8")


# ---------------------------------------------------------------------------
# Per-lane bitwise parity: batched advance == N standalone runs
# ---------------------------------------------------------------------------


LANE_STEPS = [5, 3, 5, 1]


def test_diffusion_batched_parity_heterogeneous_steps():
    """The serving contract: every lane of a (space-sharded, lane-
    sharded) batched advance is bitwise-equal to a standalone run of
    its own length — the per-lane freeze select is exact."""
    B, n = 4, max(LANE_STEPS)
    cfg = DiffusionConfig(global_shape=(16, 16), nt=8, warmup=0,
                          dtype="f64", dims=(1, 2))
    m = HeatDiffusion(cfg, devices=jax.devices()[:2])
    adv_b, bg = m.batched_advance_fn(batch=B, batch_dims=2)
    T0, Cp = m.init_state()
    lanes = np.stack(
        [np.asarray(T0) * (1 + 0.1 * i) for i in range(B)]
    )
    out = np.asarray(adv_b(
        _put(lanes, bg.sharding),
        _put(Cp, bg.aux_sharding),
        _put(np.array(LANE_STEPS, np.int32), bg.batch_sharding),
        n,
    ))
    adv1 = m.advance_fn("shard")
    for i in range(B):
        ref = np.asarray(adv1(
            _put(lanes[i], m.grid.sharding), Cp, LANE_STEPS[i]
        ))
        assert np.array_equal(out[i], ref), f"lane {i}"


def test_wave_batched_parity():
    B, n = 4, max(LANE_STEPS)
    cfg = WaveConfig(global_shape=(16, 16), nt=8, warmup=0,
                     dtype="f64", dims=(1, 2))
    w = AcousticWave(cfg, devices=jax.devices()[:2])
    adv_b, bg = w.batched_advance_fn(batch=B, batch_dims=2)
    U0, _, C2 = w.init_state()
    ul = np.stack([np.asarray(U0) * (1 + 0.1 * i) for i in range(B)])
    oU, oUp = adv_b(
        _put(ul, bg.sharding), _put(ul.copy(), bg.sharding),
        _put(C2, bg.aux_sharding),
        _put(np.array(LANE_STEPS, np.int32), bg.batch_sharding), n,
    )
    oU, oUp = np.asarray(oU), np.asarray(oUp)
    adv1 = w.advance_fn("shard")
    for i in range(B):
        rU, rUp = adv1(
            _put(ul[i], w.grid.sharding),
            _put(ul[i].copy(), w.grid.sharding), C2, LANE_STEPS[i],
        )
        assert np.array_equal(oU[i], np.asarray(rU)), f"lane {i} U"
        assert np.array_equal(oUp[i], np.asarray(rUp)), f"lane {i} U⁻"


def test_swe_batched_parity():
    B, n = 4, max(LANE_STEPS)
    cfg = SWEConfig(global_shape=(16, 16), nt=8, warmup=0,
                    dtype="f64", dims=(1, 2))
    s = ShallowWater(cfg, devices=jax.devices()[:2])
    adv_b, bg = s.batched_advance_fn(batch=B, batch_dims=2)
    h0, _ = s.init_state()
    Mus = s.face_masks()
    hl = np.stack([np.asarray(h0) * (1 + 0.1 * i) for i in range(B)])
    zeros_b = np.zeros((B,) + cfg.global_shape)
    oh, ous = adv_b(
        _put(hl, bg.sharding),
        tuple(_put(zeros_b, bg.sharding) for _ in range(2)),
        tuple(_put(M, bg.aux_sharding) for M in Mus),
        _put(np.array(LANE_STEPS, np.int32), bg.batch_sharding), n,
    )
    oh = np.asarray(oh)
    adv1 = s.advance_fn("shard")
    for i in range(B):
        rh, rus = adv1(
            _put(hl[i], s.grid.sharding),
            tuple(_put(np.zeros(cfg.global_shape), s.grid.sharding)
                  for _ in range(2)),
            Mus, LANE_STEPS[i],
        )
        assert np.array_equal(oh[i], np.asarray(rh)), f"lane {i} h"
        for a in range(2):
            assert np.array_equal(
                np.asarray(ous[a])[i], np.asarray(rus[a])
            ), f"lane {i} u{a}"


def test_diffusion_batched_deep_parity():
    """The batched deep sweep (make_deep_sweep on a BatchedGrid, jnp
    local form) matches the standalone jnp deep schedule per lane."""
    import functools

    from jax import lax

    from rocm_mpi_tpu.parallel.deep_halo import make_deep_sweep

    B = 4
    cfg = DiffusionConfig(global_shape=(16, 16), nt=8, warmup=0,
                          dtype="f64", dims=(1, 2))
    m = HeatDiffusion(cfg, devices=jax.devices()[:2])
    adv_b, bg, k = m.batched_deep_advance_fn(batch=B, batch_dims=2,
                                             block_steps=4)
    assert k == 4
    T0, Cp = m.init_state()
    lanes = np.stack(
        [np.asarray(T0) * (1 + 0.1 * i) for i in range(B)]
    )
    out = np.asarray(adv_b(
        _put(lanes, bg.sharding), _put(Cp, bg.aux_sharding), 8
    ))

    sched = make_deep_sweep(m.grid, 4, cfg.lam, cfg.jax_dtype(cfg.dt),
                            cfg.spacing, local_form="jnp")

    @functools.partial(jax.jit, donate_argnums=0)
    def adv1(T, Cp_, ns):
        Cm = sched.prepare(Cp_)
        return lax.fori_loop(
            0, ns // 4, lambda _, x: sched.sweep(x, Cm), T
        )

    for i in range(B):
        ref = np.asarray(adv1(_put(lanes[i], m.grid.sharding), Cp, 8))
        assert np.array_equal(out[i], ref), f"deep lane {i}"


def test_batched_deep_rejects_stateful_wire():
    from rocm_mpi_tpu.parallel.deep_halo import make_deep_sweep

    bg = pmesh.init_batched_grid(2, 16, 16, space_dims=(1, 1),
                                 batch_dims=1, devices=jax.devices()[:1])
    with pytest.raises(ValueError, match="stateful"):
        make_deep_sweep(bg, 4, 1.0, 0.1, (0.5, 0.5), wire_mode="int8")


def test_batched_advance_rejects_pallas_variants():
    cfg = DiffusionConfig(global_shape=(16, 16), nt=8, warmup=0,
                          dtype="f64", dims=(1, 1))
    m = HeatDiffusion(cfg, devices=jax.devices()[:1])
    with pytest.raises(ValueError, match="single-lane"):
        m.batched_advance_fn(batch=2, variant="perf")


# ---------------------------------------------------------------------------
# Bin keys, buckets, packing
# ---------------------------------------------------------------------------


def test_bin_key_round_trip():
    req = Request(request_id="r1", workload="swe",
                  global_shape=(24, 48), dtype="f32", nt=37,
                  physics=(("g", 9.81), ("H0", 2.0)),
                  wire_mode="bf16")
    key = sbins.bin_key(req)
    assert key.steps_bucket == 64
    assert key.physics == (("H0", 2.0), ("g", 9.81))  # sorted
    parsed = sbins.BinKey.parse(key.key_str())
    assert parsed == key


def test_bin_key_physics_order_cannot_split_a_bin():
    a = Request(request_id="a", physics=(("lam", 2.0), ("cp0", 3.0)))
    b = Request(request_id="b", physics=(("cp0", 3.0), ("lam", 2.0)))
    assert sbins.bin_key(a) == sbins.bin_key(b)


def test_steps_bucket():
    assert [sbins.steps_bucket(n) for n in (1, 2, 3, 8, 9, 64, 65)] == \
        [1, 2, 4, 8, 16, 64, 128]
    with pytest.raises(ValueError):
        sbins.steps_bucket(0)


@pytest.mark.parametrize("n,max_w,floor,want", [
    (1, 8, 0.5, [1]),
    (2, 8, 0.5, [2]),
    (3, 8, 0.5, [4]),
    (5, 8, 0.5, [8]),
    (9, 8, 0.5, [8, 1]),
    (5, 8, 0.8, [4, 1]),  # the split rule: 5/8 < 0.8 -> narrower class
    (13, 4, 0.5, [4, 4, 4, 1]),
])
def test_plan_batches(n, max_w, floor, want):
    assert sbins.plan_batches(n, max_w, floor) == want
    # determinism: same inputs, same plan
    assert sbins.plan_batches(n, max_w, floor) == want


def test_bin_stats_waste_accounting():
    st = sbins.BinStats(key=sbins.bin_key(Request(request_id="x")))
    st.note_batch(4, [6, 3, 6], 6)  # one idle lane + one short lane
    assert st.occupancy == 0.75
    assert st.padding_waste == pytest.approx(1 - 15 / 24)
    st.note_batch(1, [6], 6, split=True)
    assert st.splits == 1


# ---------------------------------------------------------------------------
# Request schema + queue
# ---------------------------------------------------------------------------


def test_request_record_round_trip(tmp_path):
    req = Request(request_id="rt-1", workload="wave",
                  global_shape=(16, 16), dtype="f64", nt=9,
                  physics=(("c0", 2.0),), ic_scale=1.25,
                  session="s1")
    rec = request_to_record(req)
    assert validate_request_record(rec) == []
    assert request_from_record(rec) == req
    path = tmp_path / "trace.jsonl"
    path.write_text(json.dumps(rec) + "\n\n" + json.dumps(rec) + "\n")
    assert load_trace(path) == [req, req]


def test_request_validation():
    with pytest.raises(ValueError, match="workload"):
        Request(request_id="x", workload="plasma")
    with pytest.raises(ValueError, match="nt"):
        Request(request_id="x", nt=0)
    with pytest.raises(ValueError, match="session"):
        Request(request_id="x", resume=True)
    bad = request_to_record(Request(request_id="ok"))
    bad["nt"] = -2
    assert any("nt" in p for p in validate_request_record(bad))


def test_queue_fifo_requeue_front():
    q = RequestQueue()
    t1 = q.submit(Request(request_id="a"))
    t2 = q.submit(Request(request_id="b"))
    got = q.pop_pending()
    assert [t.request.request_id for t in got] == ["a", "b"]
    q.requeue([t1])
    t3 = q.submit(Request(request_id="c"))
    got2 = q.pop_pending()
    assert [t.request.request_id for t in got2] == ["a", "c"]
    assert q.counters()["requeued"] == 1
    del t2, t3


# ---------------------------------------------------------------------------
# The service driver
# ---------------------------------------------------------------------------


def _mixed_trace(tag: str, scale0: float = 1.0):
    mix = [
        ("diffusion", (16, 16), 5), ("diffusion", (16, 16), 7),
        ("diffusion", (24, 24), 6), ("wave", (16, 16), 5),
        ("swe", (16, 16), 4), ("diffusion", (16, 16), 3),
    ]
    return [
        Request(request_id=f"{tag}-{i}", workload=wl, global_shape=sh,
                dtype="f64", nt=nt, ic_scale=scale0 + 0.05 * i)
        for i, (wl, sh, nt) in enumerate(mix)
    ]


def test_service_trace_bins_programs_steady_and_parity():
    """The acceptance shape, in-process: a heterogeneous trace (3 shape
    classes, mixed physics/steps) compiles exactly len(bins) programs,
    compiles.steady_state == 0, a repeat trace compiles NOTHING, and
    every result is bitwise-equal to its standalone twin."""
    compiles.install()
    svc = SimulationService(config=ServeConfig(max_width=4))
    trace = _mixed_trace("e2e")
    tickets = [svc.queue.submit(r) for r in trace]
    report = svc._drain_all()
    assert report.served == len(trace) and report.failed == 0
    assert report.n_programs == len(report.programs)
    assert report.n_programs == report.n_bins + sum(
        max(len(st.widths) - 1, 0) for st in report.bins.values()
    )
    assert report.compiles["steady_state"] == 0

    # steady state: the identical mix again compiles zero new programs
    before = compiles.snapshot()["totals"]["backend_compiles"]
    report2 = svc.run_trace(_mixed_trace("e2e2"))
    after = compiles.snapshot()["totals"]["backend_compiles"]
    assert after == before, "steady-state recompile"
    assert report2.compiles["steady_state"] == 0

    # bitwise parity vs standalone twins (lane 0 and lane 1 share a bin)
    r0 = tickets[0].result(timeout=5)
    cfg = DiffusionConfig(global_shape=(16, 16), nt=8, warmup=0,
                          dtype="f64", dims=(1, 1))
    m = HeatDiffusion(cfg, devices=jax.devices()[:1])
    T0, Cp = m.init_state()
    adv = m.advance_fn("shard")
    ref = np.asarray(adv(
        jnp.asarray(np.asarray(T0) * trace[0].ic_scale), Cp,
        trace[0].nt,
    ))
    assert np.array_equal(r0[0], ref)

    wv = tickets[3].result(timeout=5)
    wcfg = WaveConfig(global_shape=(16, 16), nt=8, warmup=0,
                      dtype="f64", dims=(1, 1))
    w = AcousticWave(wcfg, devices=jax.devices()[:1])
    U0, _, C2 = w.init_state()
    U0s = np.asarray(U0) * trace[3].ic_scale
    wadv = w.advance_fn("shard")
    rU, rUp = wadv(jnp.asarray(U0s), jnp.asarray(U0s.copy()), C2,
                   trace[3].nt)
    assert np.array_equal(wv[0], np.asarray(rU))
    assert np.array_equal(wv[1], np.asarray(rUp))


@pytest.mark.parametrize("workload", ["diffusion", "wave", "swe"])
def test_adapter_round_trips_a_request(workload):
    """Each workload's adapter builds its batched program and runs it
    through the shared build/run (the models' state/aux split): every
    state leaf of every lane comes back bitwise-equal to the standalone
    per-step advance of that lane's own step count."""
    from rocm_mpi_tpu.serving import service

    ad = service._ADAPTERS[workload]
    key = sbins.bin_key(Request(request_id="a", workload=workload,
                                global_shape=(16, 16), dtype="f64", nt=5))
    model = ad.make_model(ad.make_config(key, (1, 1)))
    prog = service._Program(*ad.build(model, 2, 1), ad)
    leaves = tuple(
        service._to_global(np.stack([leaf] * 2), prog.bgrid.sharding)
        for leaf in prog.base_np
    )
    steps = service._to_global(np.array([5, 3], np.int32),
                               prog.bgrid.batch_sharding)
    out = ad.run(prog, leaves, steps, 5)
    for lane, n in ((0, 5), (1, 3)):
        state, aux = model.state_and_aux()
        ref = jax.tree.leaves(model.advance_fn("shard")(*state, aux, n))
        for got, want in zip(out, ref, strict=True):
            assert np.array_equal(np.asarray(got)[lane], np.asarray(want))


def test_service_manifest_schema_and_cli_gate(tmp_path):
    svc = SimulationService(config=ServeConfig(max_width=4))
    svc.run_trace(_mixed_trace("man"))
    path = tmp_path / "serve-manifest.json"
    doc = svc.write_manifest(path)
    assert sbins.validate_manifest_doc(doc) == []
    trace_path = tmp_path / "serve-requests.jsonl"
    with open(trace_path, "w") as fh:
        for r in _mixed_trace("man"):
            fh.write(json.dumps(request_to_record(r)) + "\n")

    from rocm_mpi_tpu.telemetry.regress import check_schema

    assert check_schema([path, trace_path]) == []
    # doctored manifest: occupancy outside [0,1] must fail the gate
    doc["bins"][0]["occupancy"] = 1.7
    bad = tmp_path / "bad-manifest.json"
    bad.write_text(json.dumps(doc))
    assert any("occupancy" in p for p in check_schema([bad]))


def test_service_unknown_physics_fails_request_loudly():
    svc = SimulationService(config=ServeConfig(max_width=2))
    t = svc.queue.submit(Request(
        request_id="bad-phys", workload="diffusion",
        global_shape=(16, 16), dtype="f64", nt=2,
        physics=(("viscosity", 1.0),),
    ))
    report = svc._drain_all()
    assert report.failed == 1 and report.served == 0
    with pytest.raises(RuntimeError, match="physics"):
        t.result(timeout=5)


def test_service_session_checkpoint_multiplex_and_resume(tmp_path):
    """Per-session checkpoints ride the PR-6 manifest machinery: a
    served session banks a step-nt checkpoint whose manifest meta
    carries the request id; a resume request continues from it and the
    two-leg result is bitwise-equal to one uninterrupted run."""
    from rocm_mpi_tpu.utils import checkpoint as ckpt

    sessions = tmp_path / "sessions"
    svc = SimulationService(config=ServeConfig(
        max_width=2, sessions_dir=str(sessions),
    ))
    leg1 = Request(request_id="leg1", workload="diffusion",
                   global_shape=(16, 16), dtype="f64", nt=4,
                   ic_scale=1.1, session="sess-a")
    t1 = svc.queue.submit(leg1)
    svc._drain_all()
    assert t1.result(timeout=5) is not None
    sdir = sessions / "sess-a"
    assert ckpt.latest_valid_step(sdir) == 4
    manifest = ckpt.read_manifest(sdir, 4)
    assert manifest["meta"]["extra"]["serving"]["request_id"] == "leg1"

    # leg 2: resume to nt=9 (5 more steps)
    leg2 = Request(request_id="leg2", workload="diffusion",
                   global_shape=(16, 16), dtype="f64", nt=9,
                   ic_scale=1.1, session="sess-a", resume=True)
    t2 = svc.queue.submit(leg2)
    svc._drain_all()
    out = t2.result(timeout=5)
    assert t2.start_step == 4 and t2.steps_run == 5

    cfg = DiffusionConfig(global_shape=(16, 16), nt=16, warmup=0,
                          dtype="f64", dims=(1, 1))
    m = HeatDiffusion(cfg, devices=jax.devices()[:1])
    T0, Cp = m.init_state()
    adv = m.advance_fn("shard")
    ref = np.asarray(adv(jnp.asarray(np.asarray(T0) * 1.1), Cp, 9))
    assert np.array_equal(out[0], ref)


def test_resume_past_nt_fails_that_lane_only(tmp_path):
    """A session already past the requested nt has no checkpoint to
    hand back: the lane fails loudly — and ONLY that lane; a valid
    co-batched neighbor still gets served (tenant isolation)."""
    sessions = tmp_path / "sessions"
    svc = SimulationService(config=ServeConfig(
        max_width=2, sessions_dir=str(sessions),
    ))
    svc.run_trace([Request(
        request_id="seed", workload="diffusion", global_shape=(16, 16),
        dtype="f64", nt=4, session="sess-b",
    )])
    bad = svc.queue.submit(Request(
        request_id="past", workload="diffusion", global_shape=(16, 16),
        dtype="f64", nt=2, session="sess-b", resume=True,
    ))
    good = svc.queue.submit(Request(
        request_id="fresh", workload="diffusion", global_shape=(16, 16),
        dtype="f64", nt=2,
    ))
    report = svc._drain_all()
    assert report.failed == 1
    with pytest.raises(RuntimeError, match="already at step"):
        bad.result(timeout=5)
    assert good.result(timeout=5) is not None


def test_requeued_ticket_result_returns_none_promptly():
    q = RequestQueue()
    t = q.submit(Request(request_id="r"))
    q.pop_pending()
    q.requeue([t])
    # No timeout burn: the requeue wakes waiters immediately.
    assert t.result(timeout=5) is None
    assert t.state == "requeued"
    # Re-popped by the next drain: the wait re-arms for the real result.
    q.pop_pending()
    assert t.state == "running" and not t.done()


def test_transient_batch_error_retries_then_serves(monkeypatch):
    """A non-ValueError batch failure (e.g. checkpoint corruption is a
    RuntimeError) is TRANSIENT: the tickets ride the retry budget and
    the retried batch serves them — never strands popped tickets in
    'running', never kills the drain, and never dies on first fault."""
    from rocm_mpi_tpu.resilience.policy import RequestRetryPolicy

    svc = SimulationService(config=ServeConfig(
        max_width=1, retry=RequestRetryPolicy(budget=2,
                                              backoff_base_s=0.0),
        # the drill monkeypatches the SERIAL chokepoint; the pipelined
        # editions of this failure class live in
        # test_pipelined_prepare_failure_retries / _resolve_failure
        pipeline_depth=1,
    ))
    orig = svc._execute_batch
    calls = {"n": 0}

    def flaky(key, tickets, width, split):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("storage bit rot")
        return orig(key, tickets, width, split)

    monkeypatch.setattr(svc, "_execute_batch", flaky)
    t1 = svc.queue.submit(Request(
        request_id="x1", workload="diffusion", global_shape=(16, 16),
        dtype="f64", nt=2,
    ))
    t2 = svc.queue.submit(Request(
        request_id="x2", workload="diffusion", global_shape=(16, 16),
        dtype="f64", nt=3,
    ))
    report = svc._drain_all()
    assert report.failed == 0 and report.served == 2
    assert t1.retries == 1 and t1.state == "done"
    assert t1.result(timeout=5) is not None
    assert t2.result(timeout=5) is not None


def test_retry_budget_exhausted_quarantines(tmp_path, monkeypatch):
    """A request that fails EVERY batch it joins must not be re-batched
    forever: after the retry budget it is terminally quarantined, its
    full record banked to the append-only ledger for offline repro —
    and the accounting invariant still balances."""
    from rocm_mpi_tpu.resilience.policy import (
        CircuitPolicy,
        RequestRetryPolicy,
    )
    from rocm_mpi_tpu.serving.queue import (
        load_quarantine,
        validate_quarantine_record,
    )

    qpath = tmp_path / "quarantine.jsonl"
    svc = SimulationService(config=ServeConfig(
        max_width=1,
        retry=RequestRetryPolicy(budget=2, backoff_base_s=0.0),
        # the breaker would otherwise open mid-drill and reject the
        # retries before the budget empties
        circuit=CircuitPolicy(k=0),
        quarantine_path=str(qpath),
        pipeline_depth=1,  # the drill monkeypatches the serial seam
    ))

    def always_broken(key, tickets, width, split):
        raise RuntimeError("poison program class")

    monkeypatch.setattr(svc, "_execute_batch", always_broken)
    t = svc.queue.submit(Request(
        request_id="poison-1", workload="diffusion",
        global_shape=(16, 16), dtype="f64", nt=2, ic_scale=1.5,
    ))
    report = svc._drain_all()
    assert report.quarantined == 1 and report.failed == 0
    assert t.state == "quarantined" and t.retries == 2
    with pytest.raises(RuntimeError, match="quarantined"):
        t.result(timeout=5)
    assert svc.queue.check_accounting() == []

    records = load_quarantine(qpath)
    assert len(records) == 1
    rec = records[0]
    assert validate_quarantine_record(rec) == []
    assert rec["request_id"] == "poison-1"
    assert rec["retries"] == 2
    # the FULL request record rides along for offline repro
    from rocm_mpi_tpu.serving.queue import request_from_record

    replay = request_from_record(rec["request"])
    assert replay.ic_scale == 1.5 and replay.nt == 2

    from rocm_mpi_tpu.telemetry.regress import check_schema

    assert check_schema([qpath]) == []
    # a doctored record (no error, negative retries) fails the gate
    import copy

    bad = copy.deepcopy(rec)
    bad["retries"] = -1
    del bad["error"]
    bad_path = tmp_path / "bad-quarantine.jsonl"
    bad_path.write_text(json.dumps(bad) + "\n")
    assert check_schema([bad_path]) != []


def test_deadline_expires_pending_at_pop():
    """A pending ticket past its deadline fails with deadline-exceeded
    AT POP TIME — it never occupies a lane; a ticket with headroom
    serves normally (docs/SERVING.md "SLOs and admission")."""
    q = RequestQueue()
    stale = q.submit(Request(request_id="stale", deadline_s=1e-6))
    fresh = q.submit(Request(request_id="fresh", deadline_s=3600.0))
    popped = q.pop_pending()
    assert [t.request.request_id for t in popped] == ["fresh"]
    assert stale.state == "expired"
    with pytest.raises(RuntimeError, match="deadline-exceeded"):
        stale.result(timeout=5)
    c = q.counters()
    assert c["expired"] == 1
    assert [t.request.request_id for t in q.take_expired()] == ["stale"]
    assert q.check_accounting(in_flight=1) == []


def test_deadline_validation():
    with pytest.raises(ValueError, match="deadline_s"):
        Request(request_id="x", deadline_s=-1.0)
    rec = request_to_record(Request(request_id="ok", deadline_s=2.5))
    assert rec["deadline_s"] == 2.5
    assert request_from_record(rec).deadline_s == 2.5
    rec["deadline_s"] = 0
    assert any("deadline_s" in p for p in validate_request_record(rec))


def test_queue_full_rejects_fast_with_retry_after():
    """Admission control: an over-depth submit returns a terminally
    rejected ticket carrying a retry-after hint — fast, never silently
    dropped — and the books still balance."""
    q = RequestQueue(max_depth=2)
    a = q.submit(Request(request_id="a"))
    b = q.submit(Request(request_id="b"))
    c = q.submit(Request(request_id="c"))
    assert c.state == "rejected" and c.done()
    assert "queue-full" in c.error and "retry-after" in c.error
    with pytest.raises(RuntimeError, match="queue-full"):
        c.result(timeout=5)
    assert q.depth() == 2
    counters = q.counters()
    assert counters["rejected"] == 1 and counters["submitted"] == 3
    assert q.check_accounting() == []
    assert q.retry_after_hint() > 0
    del a, b


def test_retry_after_hint_bounded_on_cold_start():
    """Satellite: with ZERO (or one) completion marks the throughput
    window is empty — a cold-start flood used to derive a degenerate
    0/∞ hint from it. The hint must fall back to the bounded default,
    never 0, never unbounded."""
    q = RequestQueue()
    for i in range(4):
        q.submit(Request(request_id=f"cold{i}"))
    assert q.retry_after_hint() == DEFAULT_RETRY_AFTER_S
    # one mark is still not a window (span needs two endpoints)
    q.note_completed(1)
    assert q.retry_after_hint() == DEFAULT_RETRY_AFTER_S
    # and the queue-full fast-reject path carries the same bounded hint
    q2 = RequestQueue(max_depth=1)
    q2.submit(Request(request_id="a"))
    rej = q2.submit(Request(request_id="b"))
    assert f"retry-after ~{DEFAULT_RETRY_AFTER_S:.2f}s" in rej.error


def test_retry_after_hint_edges_are_clamped():
    """Satellite: every derived-hint edge is pinned into
    [0.01, MAX_RETRY_AFTER_S] — a slow window clamps at the cap, a
    stale window (post-flood idle) and a same-instant burst (span 0)
    fall back to the default, and a fast window never rounds to 0."""
    now = time.monotonic()
    q = RequestQueue()
    for i in range(4):
        q.submit(Request(request_id=f"e{i}"))
    # slow window: 2 completions over 40 s, depth 4 -> 80 s derived,
    # clamped to the cap (an honest "come back much later", bounded)
    q._done_marks[:] = [(now - 50.0, 1), (now - 10.0, 1)]
    assert q.retry_after_hint() == MAX_RETRY_AFTER_S
    # stale window: the newest mark is past RETRY_WINDOW_STALE_S —
    # extrapolating a dead window would be near-infinite; default wins
    q._done_marks[:] = [
        (now - RETRY_WINDOW_STALE_S - 40.0, 8),
        (now - RETRY_WINDOW_STALE_S - 1.0, 8),
    ]
    assert q.retry_after_hint() == DEFAULT_RETRY_AFTER_S
    # span 0: a same-instant completion burst has no rate; default wins
    # (the old derivation divided by it)
    q._done_marks[:] = [(now - 1.0, 3), (now - 1.0, 5)]
    assert q.retry_after_hint() == DEFAULT_RETRY_AFTER_S
    # fast window: huge throughput must floor at 0.01, never 0 — a 0
    # hint invites an instant re-submit hammer
    fast = RequestQueue()
    fast.submit(Request(request_id="f0"))
    fast._done_marks[:] = [(now - 2.0, 1000), (now - 1.0, 1000)]
    assert fast.retry_after_hint() == 0.01


def test_expire_overdue_uses_the_caller_clock():
    """The fleet router's single-writer wall-clock hook: a replica
    queue runs wall_slo=False (no local clock makes SLO decisions),
    and expire_overdue(now=...) expires with the ROUTER's clock — no
    sleeping, the caller just says what time it is."""
    q = RequestQueue()
    q.wall_slo = False
    slow = q.submit(Request(request_id="slow", deadline_s=5.0))
    fresh = q.submit(Request(request_id="fresh", deadline_s=3600.0))
    expired = q.expire_overdue(now=slow.submitted_mono + 10.0)
    assert [t.request.request_id for t in expired] == ["slow"]
    assert slow.state == "expired" and "router clock" in slow.error
    with pytest.raises(RuntimeError, match="deadline-exceeded"):
        slow.result(timeout=5)
    # wall_slo off: pop skips the local deadline check entirely — the
    # fresh ticket serves, and nothing else expired behind our back
    assert [t.request.request_id for t in q.pop_pending()] == ["fresh"]
    c = q.counters()
    assert c["expired"] == 1
    assert [t.request.request_id for t in q.take_expired()] == ["slow"]
    assert q.check_accounting(in_flight=1) == []
    del fresh


def test_requeue_preserves_original_relative_order():
    """Satellite: requeue-at-front is ORDER-PINNED by submission
    ordinal — a 3-ticket preemption requeue (and any sequence of
    single-ticket requeues) replays in original relative order, ahead
    of new arrivals."""
    q = RequestQueue()
    t1 = q.submit(Request(request_id="r1"))
    t2 = q.submit(Request(request_id="r2"))
    t3 = q.submit(Request(request_id="r3"))
    popped = q.pop_pending()
    assert [t.request.request_id for t in popped] == ["r1", "r2", "r3"]
    # the 3-ticket preemption requeue: one call, original order kept
    q.requeue([t1, t2, t3])
    q.submit(Request(request_id="r4"))
    assert [t.request.request_id for t in q.pop_pending()] == \
        ["r1", "r2", "r3", "r4"]
    # the ADVERSARIAL shape: per-batch retry requeues land one at a
    # time, out of submission order — the pop must still replay them
    # in original relative order (the old front-prepend had no pin).
    q.requeue([t3])
    q.requeue([t1])
    q.requeue([t2])
    assert [t.request.request_id for t in q.pop_pending()] == \
        ["r1", "r2", "r3"]


def test_retry_park_timeout_raises_not_none():
    """A RETRY-parked ticket is still owned by the live service: a
    result() timeout during its backoff window raises TimeoutError —
    the preemption None (an invitation to re-submit) would cause
    duplicate execution of a request that is about to be retried."""
    q = RequestQueue()
    t = q.submit(Request(request_id="rp"))
    q.pop_pending()
    q.requeue([t], wake=False)
    with pytest.raises(TimeoutError):
        t.result(timeout=0.05)
    # the preemption park keeps its prompt-None contract
    q.pop_pending()
    q.requeue([t], wake=True)
    assert t.result(timeout=5) is None


def test_retry_backoff_parks_until_eligible():
    """A backoff-parked ticket stays in place at pop time (FIFO
    position preserved) and becomes eligible once not_before passes."""
    q = RequestQueue()
    t1 = q.submit(Request(request_id="b1"))
    t2 = q.submit(Request(request_id="b2"))
    q.pop_pending()
    t1.not_before = time.monotonic() + 30.0
    q.requeue([t1, t2], wake=False)
    popped = q.pop_pending()
    assert [t.request.request_id for t in popped] == ["b2"]
    assert q.depth() == 1
    delay = q.next_ready_delay()
    assert delay is not None and 25.0 < delay <= 30.0
    t1.not_before = 0.0
    assert [t.request.request_id for t in q.pop_pending()] == ["b1"]


def test_circuit_breaker_opens_and_half_open_recovers():
    """The breaker arc (docs/SERVING.md "SLOs and admission"): K=3
    consecutive injected batch errors open one program class — its
    pending requests reject fast with circuit-open while a healthy
    class keeps serving — and after the cooldown a single half-open
    probe recovers it."""
    from rocm_mpi_tpu.resilience import faults
    from rocm_mpi_tpu.resilience.policy import (
        CircuitPolicy,
        RequestRetryPolicy,
    )

    svc = SimulationService(config=ServeConfig(
        max_width=2,
        retry=RequestRetryPolicy(budget=1, backoff_base_s=0.0),
        circuit=CircuitPolicy(k=3, cooldown_drains=2),
    ))
    # Drain 1 executes the (16,16) class's three width-2 batches first
    # (sorted bin keys), then (24,24): the three errors strike exactly
    # the first class.
    faults.install(
        "batch-error@step=1;batch-error@step=2;batch-error@step=3"
    )
    try:
        sick, healthy = [], []
        for i in range(6):
            sick.append(svc.queue.submit(Request(
                request_id=f"sick-{i}", workload="diffusion",
                global_shape=(16, 16), dtype="f64", nt=3,
            )))
        for i in range(2):
            healthy.append(svc.queue.submit(Request(
                request_id=f"ok-{i}", workload="diffusion",
                global_shape=(24, 24), dtype="f64", nt=3,
            )))
        svc._drain_all()
        key = sbins.bin_key(sick[0].request)
        br = svc._breakers[key]
        assert br.state == "open"
        for t in healthy:
            assert t.state == "done", (t.request.request_id, t.error)
        # the open class rejected its (retried) tickets fast
        rejected = [t for t in sick if t.state == "rejected"]
        assert rejected and all(
            "circuit-open" in t.error for t in rejected
        )
        # cooldown passes as empty drains tick by
        svc.drain_once()
        svc.drain_once()
        probe = svc.queue.submit(Request(
            request_id="probe", workload="diffusion",
            global_shape=(16, 16), dtype="f64", nt=3,
        ))
        svc._drain_all()
        assert probe.state == "done", probe.error
        assert br.state == "closed"
        assert svc.queue.check_accounting() == []
    finally:
        faults.install(None)


def test_combined_chaos_drill(tmp_path, monkeypatch):
    """SATELLITE 3 — the combined chaos drill: one deterministic run
    with faults across all three layers — a queue-flood admission storm
    (grammar-driven), TWO NaN-poisoned lanes, a SIGTERM eviction at a
    batch boundary, and an injected storage outage on a session save —
    asserting the co-batched healthy tenants stay BITWISE-equal to
    their standalone twins and every submitted ticket is terminally
    accounted."""
    from rocm_mpi_tpu.resilience import faults
    from rocm_mpi_tpu.resilience.policy import RequestRetryPolicy
    from rocm_mpi_tpu.serving.queue import load_quarantine
    from rocm_mpi_tpu.utils import checkpoint as ckpt

    sessions = tmp_path / "sessions"
    qpath = tmp_path / "quarantine.jsonl"
    svc = SimulationService(config=ServeConfig(
        max_width=2, max_depth=8, sessions_dir=str(sessions),
        retry=RequestRetryPolicy(budget=1, backoff_base_s=0.0),
        quarantine_path=str(qpath),
    ))
    # Ordinals are 1-based submission numbers: 2 and 4 are the poison
    # lanes (times=9 outlasts the budget so they quarantine); the
    # session save at step 6 gets a 3-attempt io-error outage that
    # exhausts the checkpoint retry ladder once.
    faults.install(
        "lane-nan@request=2,times=9;lane-nan@request=4,times=9;"
        "io-error@step=6,times=3;queue-flood=8@step=2"
    )
    try:
        h0 = svc.queue.submit(Request(
            request_id="healthy-0", workload="diffusion",
            global_shape=(16, 16), dtype="f64", nt=5, ic_scale=1.1,
        ))
        p_a = svc.queue.submit(Request(
            request_id="poison-a", workload="diffusion",
            global_shape=(16, 16), dtype="f64", nt=5, ic_scale=1.7,
        ))
        h1 = svc.queue.submit(Request(
            request_id="healthy-1", workload="diffusion",
            global_shape=(16, 16), dtype="f64", nt=5, ic_scale=1.2,
        ))
        p_b = svc.queue.submit(Request(
            request_id="poison-b", workload="diffusion",
            global_shape=(16, 16), dtype="f64", nt=5, ic_scale=1.9,
        ))
        store = svc.queue.submit(Request(
            request_id="store", workload="diffusion",
            global_shape=(16, 16), dtype="f64", nt=6,
            session="chaos-s",
        ))
        h2 = svc.queue.submit(Request(
            request_id="healthy-2", workload="diffusion",
            global_shape=(16, 16), dtype="f64", nt=6, ic_scale=1.3,
        ))

        # The SIGTERM eviction lands at the SECOND batch boundary of
        # drain 1: batch one executes, the rest requeues (rc-75 shape).
        calls = {"n": 0}
        orig_preempt = svc._preempt_requested

        def evict_once():
            calls["n"] += 1
            return calls["n"] == 2

        monkeypatch.setattr(svc, "_preempt_requested", evict_once)

        flood_tickets = []
        drain = 0
        while True:
            drain += 1
            clause = faults.serving_fault("queue-flood", step=drain)
            if clause is not None:
                for i in range(int(clause.delay_s)):
                    flood_tickets.append(svc.queue.submit(Request(
                        request_id=f"flood-{i}", workload="diffusion",
                        global_shape=(16, 16), dtype="f64", nt=2,
                        ic_scale=1.0 + 0.01 * i,
                    )))
            _, preempted = svc.drain_once()
            if preempted:
                continue  # the eviction passed; next drain resumes
            if svc.queue.depth() == 0:
                break
            delay = svc.queue.next_ready_delay()
            if delay:
                time.sleep(min(delay, 0.25))
            assert drain < 60, "chaos drill did not converge"

        # (1) terminal accounting: every submitted ticket ended in
        # exactly one terminal state
        assert svc.queue.check_accounting() == []
        c = svc.queue.counters()
        assert c["quarantined"] == 2, c
        assert c["rejected"] >= 1, c  # the flood hit the depth bound
        assert c["requeued"] >= 1, c  # the eviction parked work

        # (2) the poison lanes — and ONLY they — were expelled
        assert p_a.state == "quarantined" and p_b.state == "quarantined"
        assert len(load_quarantine(qpath)) == 2

        # (3) the storage outage cost one lane retry, then a durable save
        assert store.state == "done" and store.retries >= 1
        assert ckpt.latest_valid_step(sessions / "chaos-s") == 6

        # (4) co-batched healthy tenants: bitwise-equal to standalone
        # twins despite sharing batches with NaN lanes, an eviction,
        # and a storage outage
        cfg = DiffusionConfig(global_shape=(16, 16), nt=8, warmup=0,
                              dtype="f64", dims=(1, 1))
        m = HeatDiffusion(cfg, devices=jax.devices()[:1])
        T0, Cp = m.init_state()
        adv = m.advance_fn("shard")
        for t in (h0, h1, h2):
            out = t.result(timeout=5)
            assert out is not None, (t.request.request_id, t.state)
            ref = np.asarray(adv(
                jnp.asarray(np.asarray(T0) * t.request.ic_scale), Cp,
                t.request.nt,
            ))
            assert np.array_equal(out[0], ref), t.request.request_id
        served_flood = [t for t in flood_tickets if t.state == "done"]
        assert served_flood, "the admitted flood slice was never served"
        monkeypatch.setattr(svc, "_preempt_requested", orig_preempt)
    finally:
        faults.install(None)


def test_service_preemption_requeues_and_reports(monkeypatch):
    """A preemption notice at a batch boundary stops dispatch; the
    unserved tickets are requeued (the scheduler's rc-75 signal)."""
    svc = SimulationService(config=ServeConfig(max_width=1))
    calls = {"n": 0}

    def notice_after_first():
        calls["n"] += 1
        return calls["n"] > 1  # first batch runs, then the notice lands

    monkeypatch.setattr(svc, "_preempt_requested", notice_after_first)
    trace = [
        Request(request_id=f"p{i}", workload="diffusion",
                global_shape=(16, 16), dtype="f64", nt=2 + i)
        for i in range(3)
    ]
    report = svc.run_trace(trace)
    assert report.preempted
    assert report.served == 1
    assert report.requeued == 2
    assert svc.queue.depth() == 2  # parked for the next service


def test_serve_forever_notices_preempt_between_drains():
    """Satellite: a preemption notice that lands while the daemon is
    IDLE-POLLING (between drain passes, nothing popped) must stop the
    loop immediately — requeue nothing, report preempted — instead of
    polling straight through its grace window to the scheduler's
    SIGKILL. Before the fix an idle daemon ignored the notice until
    idle_exit_s elapsed and then reported preempted=False."""
    from rocm_mpi_tpu.resilience import preempt

    svc = SimulationService(config=ServeConfig(max_width=2))
    warm = svc.run_trace([Request(
        request_id="warm", workload="diffusion",
        global_shape=(16, 16), dtype="f64", nt=2,
    )])
    assert warm.served == 1
    preempt.request()
    try:
        t0 = time.monotonic()
        report = svc.serve_forever(idle_exit_s=30.0)
        elapsed = time.monotonic() - t0
    finally:
        preempt.reset()
    assert report.preempted is True
    assert report.served == 0 and report.requeued == 0
    assert svc.queue.depth() == 0
    assert elapsed < 5.0  # noticed at the loop top, not after idle_exit_s


def test_service_elastic_grow_and_shrink():
    """The first real ElasticPolicy consumer: a deep queue grows the
    batch rows within the device budget (programs dropped, compile
    window reopened); idle drains shrink back to min_ranks."""
    from rocm_mpi_tpu.resilience.policy import ElasticPolicy

    svc = SimulationService(config=ServeConfig(
        max_width=4,
        policy=ElasticPolicy(min_grow_interval_steps=0),
        device_budget=lambda: 2,
        grow_queue_depth=4,
        idle_shrink_drains=2,
    ))
    for i in range(4):
        svc.queue.submit(Request(
            request_id=f"g{i}", workload="diffusion",
            global_shape=(16, 16), dtype="f64", nt=3,
        ))
    assert svc.maybe_resize()
    assert svc._batch_dims == 2
    report = svc._drain_all()
    assert report.served == 4
    assert [e["event"] for e in svc._elastic] == ["serve.grow"]
    # idle shrink: empty drains past the threshold fold the rows back
    svc.drain_once()
    svc.drain_once()
    assert svc.maybe_resize()
    assert svc._batch_dims == 1
    assert [e["event"] for e in svc._elastic] == \
        ["serve.grow", "serve.shrink"]


def test_serve_status_badge():
    from rocm_mpi_tpu.telemetry import health

    beats = {
        0: {"counters": {"serve_submitted": 20, "serve_completed": 17,
                         "serve_requeued": 0}},
    }
    st = health.serve_status(beats)
    assert st["depth"] == 3
    assert health.format_serve_status(st) == "[SERVE depth=3 — 17 done]"
    beats[0]["counters"]["serve_completed"] = 20
    beats[0]["counters"]["serve_resizes"] = 1
    assert health.format_serve_status(health.serve_status(beats)) == \
        "serve idle (20 done, 1 resize(s))"
    assert health.serve_status({0: {"counters": {"step": 3}}}) is None
    assert health.format_serve_status(None) is None
    # A FAILED request leaves the backlog too — it must not read as
    # depth forever.
    beats = {
        0: {"counters": {"serve_submitted": 5, "serve_completed": 4,
                         "serve_requeued": 0, "serve_failed": 1}},
    }
    st = health.serve_status(beats)
    assert st["depth"] == 0
    assert health.format_serve_status(st) == \
        "serve idle (4 done, 1 failed)"


def test_serve_badge_shows_slo_outcomes():
    """Satellite: a poisoned/overloaded service is visible from the
    heartbeat sidecar alone — deadline misses (expired), quarantined
    poison, rejections, and retries all ride the SERVE badge, and
    every terminal outcome (plus retry hand-backs) leaves the depth
    formula."""
    from rocm_mpi_tpu.telemetry import health

    beats = {
        0: {"counters": {
            "serve_submitted": 12, "serve_completed": 6,
            "serve_requeued": 0, "serve_failed": 0,
            "serve_expired": 2, "serve_quarantined": 1,
            "serve_rejected": 2, "serve_retries": 1,
        }},
    }
    st = health.serve_status(beats)
    assert st["depth"] == 0
    assert st["expired"] == 2 and st["quarantined"] == 1
    line = health.format_serve_status(st)
    assert line == ("serve idle (6 done, 2 deadline-missed, "
                    "1 quarantined, 2 rejected, 1 retried)")


def test_quarantine_schema_spelling_pinned_against_regress():
    """telemetry.regress spells the serving schema markers locally
    (stdlib read side) — drift from serving.queue must fail loudly."""
    from rocm_mpi_tpu.serving import queue as squeue
    from rocm_mpi_tpu.telemetry import regress

    assert regress._SERVE_REQUEST_SCHEMA == squeue.REQUEST_SCHEMA
    assert regress._QUARANTINE_SCHEMA == squeue.QUARANTINE_SCHEMA


def test_manifest_queue_counters_sum_invariant_gated(tmp_path):
    """Satellite: the archived manifest's queue block carries the
    terminal counters and the schema gate enforces that they sum to
    submissions — a leaked ticket fails the gate, not just the live
    assert."""
    from rocm_mpi_tpu.telemetry.regress import check_schema

    svc = SimulationService(config=ServeConfig(max_width=4))
    svc.run_trace(_mixed_trace("inv"))
    path = tmp_path / "serve-manifest.json"
    doc = svc.write_manifest(path)
    q = doc["queue"]
    for field in ("submitted", "completed", "failed", "rejected",
                  "expired", "quarantined", "depth"):
        assert isinstance(q[field], int), field
    assert check_schema([path]) == []
    # a leaked ticket (counters no longer sum) must fail the gate
    doc["queue"]["completed"] -= 1
    bad = tmp_path / "leaky-manifest.json"
    bad.write_text(json.dumps(doc))
    assert any("sum to submissions" in p for p in check_schema([bad]))


def test_session_save_failure_is_lane_isolated():
    """A lane whose session save cannot run (no sessions_dir) fails
    ONLY its ticket; the co-batched neighbor still resolves and the
    completion accounting stays exact."""
    svc = SimulationService(config=ServeConfig(max_width=2))
    bad = svc.queue.submit(Request(
        request_id="sv-bad", workload="diffusion",
        global_shape=(16, 16), dtype="f64", nt=2, session="s-x",
    ))
    good = svc.queue.submit(Request(
        request_id="sv-good", workload="diffusion",
        global_shape=(16, 16), dtype="f64", nt=2,
    ))
    report = svc._drain_all()
    assert report.failed == 1 and report.served == 1
    with pytest.raises(RuntimeError, match="sessions_dir"):
        bad.result(timeout=5)
    assert good.result(timeout=5) is not None


def test_non_pow2_batch_dims_rounds_down_instead_of_bricking():
    """--batch-dims 3 must never brick a pow2-width batch: the rows
    round down to a dividing power of two."""
    svc = SimulationService(config=ServeConfig(
        max_width=4, batch_dims=3,
    ))
    trace = [
        Request(request_id=f"bd{i}", workload="diffusion",
                global_shape=(16, 16), dtype="f64", nt=3)
        for i in range(4)
    ]
    report = svc.run_trace(trace)
    assert report.served == 4 and report.failed == 0
    assert all(p.endswith("|bd3") for p in report.programs)


def test_serve_app_trace_mode_honors_f64(tmp_path):
    """A RECORDED f64 trace enables x64 regardless of the synthetic
    --dtype knob: the session checkpoint's manifest must record
    float64 leaves, not silently-canonicalized float32."""
    import os

    trace_path = tmp_path / "trace.jsonl"
    req = Request(request_id="f64-1", workload="diffusion",
                  global_shape=(16, 16), dtype="f64", nt=4,
                  session="s64")
    trace_path.write_text(json.dumps(request_to_record(req)) + "\n")
    sessions = tmp_path / "sessions"
    proc = subprocess.run(
        [sys.executable, str(REPO / "apps" / "serve.py"),
         "--trace", str(trace_path), "--cpu-devices", "1",
         "--sessions", str(sessions)],
        capture_output=True, text=True, cwd=REPO, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    manifest = json.loads(
        (sessions / "s64" / "manifest-4.json").read_text()
    )
    assert manifest["leaves"][0]["dtype"] == "float64"


# ---------------------------------------------------------------------------
# The batched traffic audit
# ---------------------------------------------------------------------------


def test_batched_traffic_audit_within_budget():
    from rocm_mpi_tpu.perf import traffic

    rows = traffic.audit_batched(local=16, dims=(2, 1), batch=2)
    assert [r.variant for r in rows] == ["batched2", "batched-hide2",
                                         "ladder2"]
    for row in rows:
        assert row.wire_bytes == row.wire_ideal, (
            row.variant,
            "a batched exchange must ship EXACTLY B x the single-lane "
            "wire",
        )
        assert row.ok, (
            f"{row.variant} ratio {row.ratio:.2f} over budget"
        )
    # the hide row gates against its own committed tolerance
    assert rows[1].budget is not None and rows[1].budget >= 1.0


def test_batched_traffic_fixture_fails():
    """The doctored over-padded row (4 lanes compiled, 1 live) must
    fail — proof the audit catches the padding-inflation class the
    occupancy floor exists to split away."""
    from rocm_mpi_tpu.perf import traffic

    rows = traffic.audit_batched(local=16, dims=(2, 1), batch=2,
                                 include_batch_fixture=True)
    fixture = [r for r in rows if "fixture" in r.variant]
    assert len(fixture) == 1
    assert not fixture[0].ok
    assert fixture[0].ratio > fixture[0].budget


def test_perf_cli_batch_fixture_exits_1():
    proc = subprocess.run(
        [sys.executable, "-m", "rocm_mpi_tpu.perf",
         "--include-batch-fixture", "--no-wire", "--local", "16"],
        capture_output=True, text=True, cwd=REPO, timeout=600,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 1, proc.stderr
    assert "TRAFFIC GATE FAILED" in proc.stderr


def test_budgets_serving_block_schema_gate(tmp_path):
    from rocm_mpi_tpu.perf.traffic import load_budgets
    from rocm_mpi_tpu.telemetry.regress import check_schema

    doc = load_budgets()
    assert doc["serving"]["batch_tolerance"] >= 1.0
    doc["serving"]["occupancy_floor"] = 1.7
    bad = tmp_path / "budgets.json"
    bad.write_text(json.dumps(doc))
    assert any("occupancy_floor" in p for p in check_schema([bad]))


# ---------------------------------------------------------------------------
# The drain pipeline (ISSUE 15, docs/SERVING.md "The pipeline")
# ---------------------------------------------------------------------------


def test_diffusion_batched_hide_parity_heterogeneous_steps():
    """The lane-batched comm/compute overlap (variant "hide" through
    make_batched_overlap_step): every lane bitwise-equal to a
    standalone hide run of its own length — the paper's overlap
    tentpole at batch scale keeps the serving parity contract."""
    B = 4
    cfg = DiffusionConfig(global_shape=(16, 16), nt=8, warmup=0,
                          dtype="f64", dims=(1, 2))
    m = HeatDiffusion(cfg, devices=jax.devices()[:2])
    adv_b, bg = m.batched_advance_fn(batch=B, batch_dims=2,
                                     variant="hide")
    T0, Cp = m.init_state()
    lanes = np.stack(
        [np.asarray(T0) * (1 + 0.1 * i) for i in range(B)]
    )
    out = np.asarray(adv_b(
        _put(lanes, bg.sharding),
        _put(Cp, bg.aux_sharding),
        _put(np.array(LANE_STEPS, np.int32), bg.batch_sharding),
        max(LANE_STEPS),
    ))
    adv1 = m.advance_fn("hide")
    for i in range(B):
        ref = np.asarray(adv1(
            _put(lanes[i], m.grid.sharding), Cp, LANE_STEPS[i]
        ))
        assert np.array_equal(out[i], ref), f"lane {i}"


def test_service_serves_batched_hide_variant():
    """A variant="hide" request class compiles the lane-batched
    overlap program and serves bitwise-equal to a standalone hide run
    on the same space decomposition."""
    compiles.install()
    # Earlier tests' model-level compiles land inside THEIR services'
    # steady windows; this assertion is about this service alone.
    compiles.reset()
    svc = SimulationService(config=ServeConfig(max_width=4))
    reqs = [
        Request(request_id=f"hide-{i}", workload="diffusion",
                global_shape=(16, 16), dtype="f64", nt=4 + i,
                variant="hide", ic_scale=1.0 + 0.1 * i)
        for i in range(3)
    ]
    tickets = [svc.queue.submit(r) for r in reqs]
    report = svc._drain_all()
    assert report.served == 3 and report.failed == 0
    assert report.compiles["steady_state"] == 0
    assert all("|hide|" in p for p in report.programs)

    space_dims = pmesh.plan_dims((16, 16), len(jax.devices()))
    cfg = DiffusionConfig(global_shape=(16, 16), nt=16, warmup=0,
                          dtype="f64", dims=space_dims)
    m = HeatDiffusion(cfg)
    T0, Cp = m.init_state()
    adv = m.advance_fn("hide")
    for i, t in enumerate(tickets):
        out = t.result(timeout=5)
        ref = np.asarray(adv(
            _put(np.asarray(T0) * reqs[i].ic_scale, m.grid.sharding),
            Cp, reqs[i].nt,
        ))
        assert np.array_equal(out[0], ref), f"request {i}"


def test_pipelined_drain_bitwise_equal_to_serial(tmp_path):
    """THE pipeline acceptance: the same heterogeneous trace — three
    workloads, mixed steps, a session save, an injected transient
    batch error riding the retry budget — through the serial (depth 1)
    and double-buffered (depth 2) drains books IDENTICAL queue
    counters, bitwise-identical results per request, and
    bitwise-identical durable session checkpoints."""
    from rocm_mpi_tpu.resilience import faults
    from rocm_mpi_tpu.resilience.policy import RequestRetryPolicy
    from rocm_mpi_tpu.utils import checkpoint as ckpt

    outs, counters, saved = {}, {}, {}
    for depth in (1, 2):
        sessions = tmp_path / f"sessions{depth}"
        svc = SimulationService(config=ServeConfig(
            max_width=4, pipeline_depth=depth,
            sessions_dir=str(sessions),
            retry=RequestRetryPolicy(budget=2, backoff_base_s=0.0),
        ))
        trace = _mixed_trace(f"pp{depth}")
        trace.append(Request(
            request_id=f"pp{depth}-sess", workload="diffusion",
            global_shape=(16, 16), dtype="f64", nt=4, ic_scale=1.2,
            session="pp-sess",
        ))
        tickets = [svc.queue.submit(r) for r in trace]
        faults.install("batch-error@step=2")
        try:
            report = svc._drain_all()
        finally:
            faults.install(None)
        assert report.failed == 0 and report.quarantined == 0
        assert svc.queue.check_accounting() == []
        counters[depth] = {
            k: v for k, v in svc.queue.counters().items()
            if k != "depth"
        }
        assert counters[depth]["requeued"] >= 1, \
            "the injected batch error never exercised the retry path"
        outs[depth] = [t.result(timeout=5) for t in tickets]
        saved[depth] = np.asarray(
            ckpt.restore_state(sessions / "pp-sess", 4, like=None)[0]
        )
    assert counters[1] == counters[2], (
        "pipelined drain reordered terminal accounting"
    )
    for i, (a, b) in enumerate(zip(outs[1], outs[2])):
        for la, lb in zip(a, b):
            assert np.array_equal(np.asarray(la), np.asarray(lb)), (
                f"request {i}: pipelined != serial"
            )
    assert np.array_equal(saved[1], saved[2])


def test_pipelined_prepare_failure_retries_then_serves():
    """Pipelined edition of the transient-batch-failure contract: an
    injected batch-error at the PREPARE (dispatch-side) stage requeues
    the batch's tickets through the retry budget; the retried batch
    serves them — no stranded 'running' tickets, invariant holds."""
    from rocm_mpi_tpu.resilience import faults
    from rocm_mpi_tpu.resilience.policy import RequestRetryPolicy

    svc = SimulationService(config=ServeConfig(
        max_width=1, pipeline_depth=2,
        retry=RequestRetryPolicy(budget=2, backoff_base_s=0.0),
    ))
    t1 = svc.queue.submit(Request(
        request_id="pf1", workload="diffusion", global_shape=(16, 16),
        dtype="f64", nt=2,
    ))
    t2 = svc.queue.submit(Request(
        request_id="pf2", workload="diffusion", global_shape=(16, 16),
        dtype="f64", nt=3,
    ))
    faults.install("batch-error@step=1")
    try:
        report = svc._drain_all()
    finally:
        faults.install(None)
    assert report.failed == 0 and report.served == 2
    assert t1.retries == 1 and t1.state == "done"
    assert t2.state == "done"
    assert svc.queue.check_accounting() == []


def test_retry_after_dispatched_batch_never_reads_donated_buffer(
        monkeypatch):
    """THE async-dispatch/donation hazard drill: a batch that fails
    AFTER dispatch (at the fetch/resolve stage) retries by
    re-assembling from HOST state — the donated device buffers were
    consumed by the advance and are never re-read (a re-read would
    raise jax's deleted-array error), and the retried result stays
    bitwise-equal to a standalone run."""
    from rocm_mpi_tpu.resilience.policy import RequestRetryPolicy

    svc = SimulationService(config=ServeConfig(
        max_width=1, pipeline_depth=2,
        retry=RequestRetryPolicy(budget=2, backoff_base_s=0.0),
    ))
    orig = svc._resolve_batch
    calls = {"n": 0}

    def flaky_resolve(fl):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("device fault surfacing at fetch")
        return orig(fl)

    monkeypatch.setattr(svc, "_resolve_batch", flaky_resolve)
    t = svc.queue.submit(Request(
        request_id="donate-1", workload="diffusion",
        global_shape=(16, 16), dtype="f64", nt=5, ic_scale=1.3,
    ))
    report = svc._drain_all()
    assert report.failed == 0 and report.served == 1
    assert t.state == "done" and t.retries == 1
    out = t.result(timeout=5)
    cfg = DiffusionConfig(global_shape=(16, 16), nt=8, warmup=0,
                          dtype="f64", dims=(1, 1))
    m = HeatDiffusion(cfg, devices=jax.devices()[:1])
    T0, Cp = m.init_state()
    ref = np.asarray(m.advance_fn("shard")(
        jnp.asarray(np.asarray(T0) * 1.3), Cp, 5
    ))
    assert np.array_equal(out[0], ref)
    assert svc.queue.check_accounting() == []


def test_pipelined_same_drain_save_then_resume_matches_serial(tmp_path):
    """The session read-after-write barrier: request A saves session
    's' and request B resumes 's' in SEPARATE batches of ONE drain
    pass. The pipelined drain must flush A's resolve (the save) before
    assembling B — B resumes from step 4 in both modes and the two-leg
    result stays bitwise-equal to the serial drain's."""
    outs, starts = {}, {}
    for depth in (1, 2):
        sessions = tmp_path / f"sessions{depth}"
        svc = SimulationService(config=ServeConfig(
            max_width=1, pipeline_depth=depth,
            sessions_dir=str(sessions),
        ))
        a = svc.queue.submit(Request(
            request_id=f"rw{depth}-a", workload="diffusion",
            global_shape=(16, 16), dtype="f64", nt=4, ic_scale=1.1,
            session="rw-sess",
        ))
        b = svc.queue.submit(Request(
            request_id=f"rw{depth}-b", workload="diffusion",
            global_shape=(16, 16), dtype="f64", nt=9, ic_scale=1.1,
            session="rw-sess", resume=True,
        ))
        report = svc._drain_all()
        assert report.failed == 0 and report.served == 2
        assert a.state == "done" and b.state == "done"
        starts[depth] = (b.start_step, b.steps_run)
        outs[depth] = np.asarray(b.result(timeout=5)[0])
    assert starts[1] == (4, 5), starts
    assert starts[2] == (4, 5), (
        "the pipelined drain assembled the resume lane before the "
        f"same-drain session save landed: {starts}"
    )
    assert np.array_equal(outs[1], outs[2])


def test_failing_dispatch_hook_cannot_wedge_bubble_accounting():
    """A stage hook that raises at the dispatch stage must not leave
    the in-flight counter stuck high (which would freeze busy_s and
    report a forever-1.0 bubble): the batch fails through the normal
    routing and the NEXT drain's accounting still moves."""
    from rocm_mpi_tpu.resilience.policy import RequestRetryPolicy

    calls = {"n": 0}

    def exploding_once(stage, info):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("hook blew up at dispatch")

    svc = SimulationService(config=ServeConfig(
        max_width=1, pipeline_depth=2,
        retry=RequestRetryPolicy(budget=2, backoff_base_s=0.0),
        stage_hooks={"dispatch": exploding_once},
    ))
    t = svc.queue.submit(Request(
        request_id="hook-1", workload="diffusion",
        global_shape=(16, 16), dtype="f64", nt=3,
    ))
    report = svc._drain_all()
    assert t.state == "done" and t.retries == 1 and report.failed == 0
    assert svc._inflight_n == 0, "in-flight counter leaked"
    assert svc._pipe["busy_s"] > 0.0, (
        "busy accounting froze after the failed dispatch hook"
    )
    assert svc.queue.check_accounting() == []


def _drain_wall(depth: int, nt: int, sleep_s: float, tag: str,
                hidden: list | None = None):
    """One measured drain: 4 one-lane batches of the same bin, program
    cache warmed first so the clock sees the steady state. The resolve
    hook injects the deterministic slow host stage; with `hidden`, each
    of its sleeps that ran while a batch was in flight is appended.
    Returns (wall, the measured drain's busy seconds)."""
    svc = None

    def slow(stage, info):
        t0 = time.monotonic()
        time.sleep(sleep_s)
        if hidden is not None and svc._inflight_n > 0:
            hidden.append(time.monotonic() - t0)

    svc = SimulationService(config=ServeConfig(
        max_width=1, pipeline_depth=depth,
        stage_hooks={"resolve": slow} if sleep_s else None,
    ))

    def req(rid, scale=1.0):
        return Request(request_id=rid, workload="diffusion",
                       global_shape=(64, 64), dtype="f64", nt=nt,
                       ic_scale=scale)

    svc.run_trace([req(f"{tag}-warm")])
    for i in range(4):
        svc.queue.submit(req(f"{tag}-{i}", 1.0 + 0.01 * i))
    busy0 = svc.pipeline_stats()["busy_s"]
    t0 = time.monotonic()
    report = svc._drain_all()
    wall = time.monotonic() - t0
    assert report.served == 4, report
    return wall, svc.pipeline_stats()["busy_s"] - busy0


def test_pipelined_drain_hides_slow_host_stage():
    """The pipeline win, judged by the service's own accounting rather
    than by two wall clocks that a loaded machine can reorder: with a
    deterministically slow host resolve stage (stage hook), the
    double-buffered drain runs each of its first three slow resolves
    while the next batch is in flight, and its busy counter holds
    them; the serial drain runs none so, and its busy counter holds
    none of them."""
    sleep_s = 0.05
    serial_hidden, pipe_hidden = [], []
    serial_wall, serial_busy = _drain_wall(1, 64, sleep_s, "ser",
                                           serial_hidden)
    pipe_wall, pipe_busy = _drain_wall(2, 64, sleep_s, "pipe", pipe_hidden)
    assert serial_hidden == []
    assert len(pipe_hidden) == 3, pipe_hidden
    # The device counts as busy while a batch is in flight: the hidden
    # sleeps lie inside the pipelined drain's busy time, and the serial
    # drain's four sleeps outside its own.
    assert pipe_wall >= pipe_busy >= sum(pipe_hidden) >= 3 * sleep_s
    assert serial_busy <= serial_wall - 4 * sleep_s, (serial_busy,
                                                      serial_wall)


def test_manifest_pipeline_block_and_schema_gate(tmp_path):
    """The manifest's pipeline block (depth, batches, bubble, stage
    walls) validates — and a doctored bubble/depth fails the schema
    gate, not silently corrupts a pipeline-efficiency audit."""
    svc = SimulationService(config=ServeConfig(max_width=4))
    svc.run_trace(_mixed_trace("pipe-man"))
    path = tmp_path / "serve-manifest.json"
    doc = svc.write_manifest(path)
    pipe = doc["pipeline"]
    assert pipe["depth"] == 2 and pipe["batches"] >= 1
    assert 0.0 <= pipe["bubble"] <= 1.0
    for field in ("assemble_s", "dispatch_s", "fetch_s", "resolve_s"):
        assert pipe[field] >= 0.0
    assert sbins.validate_manifest_doc(doc) == []

    from rocm_mpi_tpu.telemetry.regress import check_schema

    assert check_schema([path]) == []
    doc["pipeline"]["bubble"] = 1.7
    bad = tmp_path / "bad-manifest.json"
    bad.write_text(json.dumps(doc))
    assert any("bubble" in p for p in check_schema([bad]))
    doc["pipeline"]["bubble"] = 0.1
    doc["pipeline"]["depth"] = 0
    bad.write_text(json.dumps(doc))
    assert any("depth" in p for p in check_schema([bad]))


def test_pipeline_gauges_learned_by_regress():
    """serve.device_bubble is lower-is-better WITH zero as evidence
    (the fully-overlapped contract — a zero baseline makes any bubble
    growth a gated regression); serve.pipeline_depth is a config echo
    and never regress-gated."""
    from rocm_mpi_tpu.telemetry.regress import compare, extract_metrics

    doc = {"gauges": {"serve.device_bubble": 0.0,
                      "serve.pipeline_depth": 2.0,
                      "run.gpts@1dev": 5.0}}
    m = extract_metrics(doc)
    assert m["gauges.serve.device_bubble"] == (0.0, "lower")
    assert "gauges.serve.pipeline_depth" not in m
    base = {"gauges": {"serve.device_bubble": 0.0}}
    cur = {"gauges": {"serve.device_bubble": 0.25}}
    assert any(d.regressed for d in compare(cur, base))


def test_lowered_audit_proves_batched_donation():
    """Tentpole (b)'s proof: every batched advance's declared donation
    — diffusion's one leaf (shard AND hide), wave's two leapfrog
    carries, SWE's h + velocity leaves — actually aliased in the
    compiled program's input_output_alias table, and the batched
    collectives stay per-space-axis partial permutations outside any
    lowered conditional."""
    from rocm_mpi_tpu.analysis import lowered

    rows = lowered.audit_batched_drivers(local=8, batch=2)
    by_name = {r.workload: r for r in rows}
    assert set(by_name) == {
        "diffusion/batched-shard", "diffusion/batched-hide",
        "wave/batched", "swe/batched",
    }
    for r in rows:
        assert r.ok, (r.workload, r.problems)
        assert r.n_collectives >= 1
    assert by_name["diffusion/batched-shard"].donated_params == 1
    assert by_name["diffusion/batched-hide"].donated_params == 1
    assert by_name["wave/batched"].donated_params == 2
    assert by_name["swe/batched"].donated_params == 3


# ---------------------------------------------------------------------------
# Acceptance drills
# ---------------------------------------------------------------------------


def test_serve_app_50_request_acceptance(tmp_path):
    """THE acceptance drill: a heterogeneous 50-request trace (3 shape
    classes, mixed physics/workloads/steps) through apps/serve.py
    compiles exactly len(bins) programs (manifest-pinned) with
    compiles.steady_state == 0, and the banked sidecars clear the
    schema gate."""
    out = tmp_path / "out"
    proc = subprocess.run(
        [sys.executable, str(REPO / "apps" / "serve.py"),
         "--synthetic", "50", "--seed", "3", "--nt-max", "16",
         "--max-width", "4", "--cpu-devices", "1",
         "--out", str(out)],
        capture_output=True, text=True, cwd=REPO, timeout=900,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "compiles.steady_state=0" in proc.stdout
    doc = json.loads((out / "serve-manifest.json").read_text())
    assert sbins.validate_manifest_doc(doc) == []
    assert doc["served"] == 50 and doc["preempted"] is False
    assert doc["compiles"]["steady_state"] == 0
    assert len(doc["bins"]) >= 3
    # exactly len(bins) programs: every program class belongs to a bin,
    # and every bin's width classes are all present
    widths = sum(len(row["widths"]) for row in doc["bins"])
    assert len(doc["programs"]) == widths
    shapes = {row["key"].split("|")[1] for row in doc["bins"]}
    assert len(shapes) >= 3

    from rocm_mpi_tpu.telemetry.regress import check_schema

    assert check_schema([out / "serve-manifest.json",
                         out / "serve-requests.jsonl"]) == []


def test_serve_daemon_sigterm_while_idle_exits_75(tmp_path):
    """Satellite: THE missing daemon drill — apps/serve.py --serve
    drains its trace, idles, and then a real SIGTERM lands BETWEEN
    drain passes. The daemon must exit rc 75 promptly (not poll
    through its grace window), requeue nothing (nothing was popped),
    and still bank a schema-valid manifest on the way out."""
    out = tmp_path / "out"
    tele = tmp_path / "tele"
    proc = subprocess.Popen(
        [sys.executable, str(REPO / "apps" / "serve.py"),
         "--serve", "--idle-exit-s", "300",
         "--synthetic", "3", "--seed", "7", "--nt-max", "3",
         "--max-width", "4", "--cpu-devices", "1",
         "--telemetry", str(tele), "--out", str(out)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "RMT_PREEMPT_GRACE_S": "30"},
    )
    try:
        # wait until the trace is fully drained (the daemon is now
        # idle-polling) by watching the live telemetry stream
        stream = tele / "telemetry-rank0.jsonl"
        deadline = time.monotonic() + 300.0
        while time.monotonic() < deadline:
            done = 0
            if stream.is_file():
                done = stream.read_text(errors="replace").count(
                    "serve.request.done")
            if done >= 3:
                break
            assert proc.poll() is None, proc.communicate()
            time.sleep(0.2)
        else:
            raise AssertionError("daemon never drained its trace")
        proc.send_signal(signal.SIGTERM)
        stdout, stderr = proc.communicate(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
    assert proc.returncode == 75, (proc.returncode, stdout[-2000:],
                                   stderr[-2000:])
    assert "rc 75" in stdout
    assert "0 requeued" in stdout  # idle notice: nothing was popped
    doc = json.loads((out / "serve-manifest.json").read_text())
    assert doc["preempted"] is True and doc["served"] == 3

    from rocm_mpi_tpu.telemetry.regress import check_schema

    assert check_schema([out / "serve-manifest.json",
                         out / "serve-requests.jsonl"]) == []


def test_serving_gloo_two_rank_drill(tmp_path):
    """Gloo-real 2-rank drill: a heterogeneous queue served by a
    2-rank space mesh compiles exactly len(bins) programs on every
    rank, with compiles.steady_state == 0 and a second identical trace
    compiling NOTHING (tests/serving_worker.py)."""
    from rocm_mpi_tpu.parallel.launcher import spawn_ranks

    results = spawn_ranks(
        [REPO / "tests" / "serving_worker.py"], nprocs=2, timeout=420,
    )
    for rank, (proc, (out, err)) in enumerate(results):
        assert proc.returncode == 0, (rank, out[-500:], err[-2000:])
        done = [l for l in out.splitlines()
                if "SERVING_WORKER_DONE" in l]
        assert len(done) == 1, out
        line = done[0]
        assert f"rank={rank}" in line
        assert "bins=4 programs=4" in line, line
        assert "steady=0" in line and "second_trace_compiles=0" in line

"""Runtime health plane tests (docs/TELEMETRY.md "Health plane"):
flight-recorder ring/counters/sidecar semantics, the progress-aware
stall verdict, post-mortem composition and the merged timeline trace
(torn sidecars included), monitor / export-openmetrics CLI exit codes
and the OpenMetrics round-trip, compile accounting with the regress
zero-pin, the unified clear_events reset, and the ISSUE-5 acceptance
drills: a real 2-rank weak-scaling launch with an injected `stall`
fault (watchdog names the rank BY PROGRESS, post-mortem carries a
faulthandler traceback and a flight ring ending in a halo span, peers
reaped by the existing grace kill, wreckage bundled) and its clean twin
(zero verdicts, compiles.steady_state == 0, regress-pinned)."""

from __future__ import annotations

import json
import pathlib

import pytest

from rocm_mpi_tpu import telemetry
from rocm_mpi_tpu.telemetry import compiles, events, flight, health, trace
from rocm_mpi_tpu.telemetry.__main__ import main as cli_main

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture(autouse=True)
def _isolated_health(monkeypatch):
    """Every test starts with telemetry and the flight recorder off and
    empty; compile accounting reset (the installed process-wide hook, if
    any, stays — uninstalling it mid-suite would be its own bug)."""
    monkeypatch.setattr(events, "_ENABLED", False)
    monkeypatch.setattr(events, "_DIR", None)
    monkeypatch.setattr(events, "_RANK", None)
    monkeypatch.setattr(flight, "_ENABLED", False)
    monkeypatch.setattr(flight, "_DIR", None)
    monkeypatch.setattr(flight, "_RANK", None)
    events.clear()
    flight.reset()
    compiles.reset()
    yield
    flight.disable()
    events.clear()
    flight.reset()
    compiles.reset()


def _beat(rank, step, phase="step", t=1000.0, extra=None):
    doc = {
        "schema": flight.HEARTBEAT_SCHEMA, "v": 1, "rank": rank, "t": t,
        "counters": {"step": step}, "last_phase": phase,
        "last_phase_name": f"{phase}.x", "last_phase_t": t, "ring": [],
    }
    if extra:
        doc.update(extra)
    return doc


# ---------------------------------------------------------------------------
# Flight recorder: ring, counters, sidecar, reset
# ---------------------------------------------------------------------------


def test_flight_sidecar_counters_and_ring(tmp_path):
    events.configure(directory=tmp_path, rank=2)
    flight.enable(rank=2)
    with telemetry.span("halo.heartbeat", phase="halo", bytes=4096):
        pass
    flight.progress(step=7, windows=1)
    flight.flush()
    doc = json.loads((tmp_path / "heartbeat-rank2.json").read_text())
    assert doc["schema"] == flight.HEARTBEAT_SCHEMA
    assert doc["rank"] == 2
    assert doc["counters"]["step"] == 7
    assert doc["counters"]["windows"] == 1
    # the events tap counted the halo span and its bytes
    assert doc["counters"]["halo_exchanges"] == 1
    assert doc["counters"]["halo_bytes"] == 4096
    # span ENTRY set the phase (a wedged rank never reaches the exit)
    assert doc["last_phase"] == "halo"
    assert doc["last_phase_name"] == "halo.heartbeat"
    # ring holds the entry note and the exit record, in order
    kinds = [(r["kind"], r["name"]) for r in doc["ring"]]
    assert ("phase", "halo.heartbeat") in kinds
    assert ("span", "halo.heartbeat") in kinds
    assert doc["ring"][-1]["kind"] == "span"


def test_flight_step_counter_is_monotonic_and_bounded_ring(tmp_path):
    events.configure(directory=tmp_path, rank=0)
    flight.enable(rank=0, ring_size=4)
    flight.progress(step=9)
    flight.progress(step=3)  # lower: ignored
    for i in range(10):
        telemetry.counter("x", i)
    doc = flight.snapshot()
    assert doc["counters"]["step"] == 9
    assert len(doc["ring"]) == 4, "ring is bounded"


def test_flight_progress_flushes_before_blocking(tmp_path):
    """The watchdog contract: a step bump is on disk synchronously —
    the caller may block in a collective immediately after."""
    events.configure(directory=tmp_path, rank=0)
    flight.enable(rank=0)
    flight.progress(step=41)
    doc = json.loads((tmp_path / "heartbeat-rank0.json").read_text())
    assert doc["counters"]["step"] == 41


def test_flight_reset_is_the_unified_clear_events(tmp_path):
    """Satellite 6: exactly one reset behavior — events dropped,
    annotation dedup preserved — shared by telemetry.clear_events, the
    deprecated metrics.clear_events alias, and flight.reset."""
    from rocm_mpi_tpu.utils import metrics

    events.configure(directory=tmp_path, rank=0)
    flight.enable(rank=0)
    telemetry.annotate("halo.exchange", bytes=128)
    telemetry.record_event("retry", attempt=1)
    with telemetry.span("s"):
        pass
    flight.reset()
    assert events.records(kind="event") == [], "events dropped"
    assert events.records(kind="span"), "spans survive the reset"
    assert telemetry.annotate("halo.exchange", bytes=128) is None, \
        "annotation dedup preserved: no re-emit after reset"
    assert flight.snapshot()["counters"] == {}
    # the deprecated alias forwards (and says so)
    telemetry.record_event("retry", attempt=2)
    with pytest.deprecated_call():
        metrics.clear_events()
    assert events.records(kind="event") == []
    # the public spelling needs no warning
    telemetry.record_event("retry", attempt=3)
    telemetry.clear_events()
    assert events.records(kind="event") == []


def test_flight_enable_needs_a_directory(monkeypatch):
    monkeypatch.delenv("RMT_HEALTH_DIR", raising=False)
    monkeypatch.delenv("RMT_TELEMETRY_DIR", raising=False)
    with pytest.raises(ValueError, match="directory"):
        flight.enable()


# ---------------------------------------------------------------------------
# Read side: sidecar loading (torn-tolerant) and the stall verdict
# ---------------------------------------------------------------------------


def test_load_heartbeats_skips_torn_sidecar(tmp_path):
    (tmp_path / "heartbeat-rank0.json").write_text(json.dumps(_beat(0, 5)))
    (tmp_path / "heartbeat-rank1.json").write_text(
        '{"schema": "rocm_mpi_tpu.telemetry.heartbeat", "counters": {"st'
    )  # killed mid-write
    beats, skipped = health.load_heartbeats(tmp_path)
    assert list(beats) == [0] and skipped == 1


def test_progress_watch_stalled_collective_signature():
    w = health.ProgressWatch(stall_grace_s=2.0)
    w.observe({0: _beat(0, 10), 1: _beat(1, 10)}, now=0.0)
    # rank 0 advances to 15 and blocks; rank 1 never changes
    w.observe({0: _beat(0, 15), 1: _beat(1, 10)}, now=1.0)
    assert w.verdicts(1.5) == [], "grace not elapsed for rank 1"
    v = w.verdicts(3.5)
    assert [x["rank"] for x in v] == [1]
    assert v[0]["step"] == 10 and v[0]["median_step"] == 12.5
    assert v[0]["stalled_for_s"] >= 2.0
    # rank 0 is NOT flagged even when it also stops changing: its
    # counter is at/above the median (it is the wedged survivor)
    assert all(x["rank"] != 0 for x in w.verdicts(30.0))


def test_progress_watch_needs_median_ahead_not_wall_clock():
    """Everyone equally slow (one long window, a coordinated compile):
    nobody's median moves past anybody — no verdict, ever."""
    w = health.ProgressWatch(stall_grace_s=1.0)
    w.observe({0: _beat(0, 10), 1: _beat(1, 10)}, now=0.0)
    assert w.verdicts(100.0) == []
    # single rank: no cross-rank median, no verdict
    w2 = health.ProgressWatch(stall_grace_s=1.0)
    w2.observe({0: _beat(0, 10)}, now=0.0)
    assert w2.verdicts(100.0) == []


def test_progress_watch_ignores_ranks_without_step_counters():
    """A rank that never published a step counter is NOT participating
    (sitting out a weak-scaling rung, still compiling) — it must be
    excluded from the median and never flagged, and a lone publishing
    rank has no cross-rank median to be judged against."""
    parked = _beat(1, 0)
    del parked["counters"]["step"]  # no step ever published
    w = health.ProgressWatch(stall_grace_s=1.0)
    w.observe({0: _beat(0, 5), 1: parked}, now=0.0)
    w.observe({0: _beat(0, 50), 1: parked}, now=2.0)
    assert w.verdicts(50.0) == [], \
        "neither the parked rank (no counter) nor the lone worker fires"
    assert list(w.steps()) == [0]


def test_progress_watch_liveness_is_not_progress():
    """A stalled rank's flusher rewrites identical counters forever
    (fresh wall stamps): content, not mtime, defines progress."""
    w = health.ProgressWatch(stall_grace_s=1.0)
    w.observe({0: _beat(0, 5, t=1.0), 1: _beat(1, 9, t=1.0)}, now=0.0)
    w.observe({0: _beat(0, 5, t=2.0), 1: _beat(1, 9, t=2.0)}, now=2.0)
    v = w.verdicts(2.5)
    assert [x["rank"] for x in v] == [0]
    assert w.ages(2.5)[0] == pytest.approx(2.5)


# ---------------------------------------------------------------------------
# Post-mortem composition + merged timeline trace (satellite 3)
# ---------------------------------------------------------------------------


def test_postmortem_compose_and_bundle_with_torn_sidecar(tmp_path):
    (tmp_path / "heartbeat-rank0.json").write_text(
        json.dumps(_beat(0, 14, phase="halo", t=1000.5))
    )
    (tmp_path / "heartbeat-rank1.json").write_text('{"torn')  # died mid-write
    (tmp_path / "postmortem-rank0.traceback").write_text(
        "Current thread 0x1 (most recent call first):\n  fault_point\n"
    )
    (tmp_path / "telemetry-rank0.jsonl").write_text(json.dumps({
        "v": 2, "kind": "span", "name": "step_window", "t": 1000.0,
        "t_mono": 1.0, "rank": 0, "dur_s": 0.25, "depth": 0, "tid": 1,
        "attrs": {"phase": "step", "steps": 5},
    }) + "\n" + '{"kind": "span", "name": "torn')
    verdict = {"rank": 0, "step": 14, "median_step": 16.5,
               "stalled_for_s": 3.0, "last_phase": "halo"}
    pm = health.write_postmortem(tmp_path, 0, verdict)
    doc = json.loads(pm.read_text())
    assert doc["schema"] == flight.POSTMORTEM_SCHEMA
    assert "fault_point" in doc["traceback"]
    assert doc["heartbeat"]["counters"]["step"] == 14
    assert isinstance(verdict.get("t"), float), "verdict wall-stamped"

    bundle_dir = health.bundle_postmortem(tmp_path, [verdict])
    bundle = json.loads((bundle_dir / "bundle.json").read_text())
    assert bundle["schema"] == flight.BUNDLE_SCHEMA
    assert bundle["verdicts"][0]["rank"] == 0
    # the merged timeline still opens with the torn sidecar in the mix:
    # JSON-valid, ts sorted, one verdict instant, a progress counter track
    tl = json.loads((bundle_dir / "timeline-trace.json").read_text())
    evs = tl["traceEvents"]
    for ev in evs:
        for key in trace.TRACE_REQUIRED_KEYS:
            assert key in ev, (key, ev)
    ts = [e["ts"] for e in evs if e["ph"] != "M"]
    assert ts == sorted(ts), "timeline must be ts-sorted"
    instants = [e for e in evs if e["name"] == "watchdog.verdict"]
    assert len(instants) == 1 and instants[0]["pid"] == 0
    assert instants[0]["args"]["median_step"] == 16.5
    counters = [e for e in evs if e["ph"] == "C" and e["name"] == "progress"]
    assert counters and counters[0]["args"]["step"] == 14
    # schema gate recognizes every bundled JSON artifact
    assert cli_main([
        "regress", "--check-schema",
        str(tmp_path / "heartbeat-rank0.json"), str(pm),
        str(bundle_dir / "bundle.json"),
    ]) == 0


def test_trace_verdict_instants_per_verdict_and_heartbeat_tracks():
    beats = {k: _beat(k, 10 + k, t=1000.0 + k) for k in (0, 1, 2)}
    verdicts = [
        {"rank": 1, "step": 3, "median_step": 5, "stalled_for_s": 2.0,
         "t": 1002.5},
        {"rank": 2, "step": 4, "median_step": 5, "stalled_for_s": 2.0,
         "t": 1003.0},
    ]
    doc = trace.to_chrome_trace({}, heartbeats=beats, verdicts=verdicts)
    evs = doc["traceEvents"]
    assert len([e for e in evs if e["name"] == "watchdog.verdict"]) == 2
    assert len([e for e in evs if e["ph"] == "C"]) == 3, \
        "one progress counter track per rank"
    assert {e["pid"] for e in evs} == {0, 1, 2}
    ts = [e["ts"] for e in evs if e["ph"] != "M"]
    assert ts == sorted(ts)


# ---------------------------------------------------------------------------
# monitor / export-openmetrics CLI (exit codes + round-trip)
# ---------------------------------------------------------------------------


def test_monitor_cli_exit_codes(tmp_path, capsys):
    assert cli_main(["monitor", str(tmp_path), "--iterations", "1"]) == 2
    (tmp_path / "heartbeat-rank0.json").write_text(json.dumps(_beat(0, 7)))
    (tmp_path / "heartbeat-rank1.json").write_text(json.dumps(_beat(1, 9)))
    assert cli_main(["monitor", str(tmp_path), "--iterations", "2",
                     "--interval", "0.05"]) == 0
    out = capsys.readouterr().out
    assert "rank" in out and "Δmedian" in out
    assert "+1" in out and "-1" in out, "straggler delta vs median shown"


def test_export_openmetrics_round_trips_run_gauges(tmp_path, capsys):
    assert cli_main(["export-openmetrics", str(tmp_path)]) == 2
    events.configure(directory=tmp_path, rank=0)
    # the exact key shapes the aggregator produces for rung gauges
    telemetry.gauge("run.gpts", 1.25, devices=4, driver="scan")
    telemetry.gauge("run.t_eff_gbs", 3.5, variant="hide")
    telemetry.counter("halo.exchange_nbytes", 2048)
    telemetry.counter("halo.exchange_nbytes", 2048)
    flight.enable(rank=0)
    flight.progress(step=12)
    assert cli_main(["export-openmetrics", str(tmp_path)]) == 0
    text = capsys.readouterr().out
    assert text.rstrip().endswith("# EOF")
    parsed = health.parse_openmetrics(text)
    assert parsed["rmt_gauge"]["run.gpts@4dev:scan"] == 1.25, \
        "rung gauge keys round-trip verbatim"
    assert parsed["rmt_gauge"]["run.t_eff_gbs"] == 3.5
    assert parsed["rmt_counter_total"]["halo.exchange_nbytes"] == 4096
    assert parsed["rmt_progress"][
        (("counter", "step"), ("rank", "0"))
    ] == 12
    # --out writes the same snapshot atomically
    out_file = tmp_path / "snap.om"
    assert cli_main(["export-openmetrics", str(tmp_path),
                     "--out", str(out_file)]) == 0
    capsys.readouterr()
    assert health.parse_openmetrics(out_file.read_text()) == parsed


# ---------------------------------------------------------------------------
# Compile accounting (telemetry/compiles.py + the regress zero-pin)
# ---------------------------------------------------------------------------

BACKEND = "/jax/core/compile/backend_compile_duration"


def test_compiles_tracker_counts_and_steady_window(tmp_path):
    events.configure(directory=tmp_path, rank=0)
    compiles.record_interval(BACKEND, "jit_step", 0.2)
    compiles.record_interval(BACKEND, "jit_step", 0.1)
    compiles.record_interval(BACKEND, "jit_probe", 0.1)
    compiles.record_interval("/jax/core/compile/jaxpr_trace_duration",
                             "jit_step", 0.1)  # not a backend compile
    compiles.record_cache_event("/jax/compilation_cache/cache_misses")
    compiles.record_cache_event("/jax/compilation_cache/cache_hits")
    assert compiles.steady_state() == 0
    compiles.mark_steady()
    compiles.record_interval(BACKEND, "jit_step", 0.3)  # a RECOMPILE
    compiles.unmark_steady()
    compiles.record_interval(BACKEND, "jit_next_rung", 0.3)  # legitimate
    snap = compiles.snapshot()
    assert snap["programs"]["jit_step"]["count"] == 3
    assert snap["programs"]["jit_step"]["steady"] == 1
    assert snap["totals"] == {"backend_compiles": 5, "cache_hits": 1,
                              "cache_misses": 1}
    assert compiles.steady_state() == 1
    compiles.emit_gauges()
    gauges = {r["name"]: r["value"] for r in events.records(kind="gauge")}
    assert gauges["compiles.total"] == 5
    assert gauges["compiles.steady_state"] == 1
    assert gauges["compiles.cache_misses"] == 1
    spans = events.records(kind="span", name="compile.backend")
    assert len(spans) == 5
    assert spans[0]["attrs"]["program"] == "jit_step"


def test_compiles_steady_gauge_only_after_mark(tmp_path):
    events.configure(directory=tmp_path, rank=0)
    compiles.record_interval(BACKEND, "jit_x", 0.1)
    compiles.emit_gauges()
    gauges = {r["name"] for r in events.records(kind="gauge")}
    assert "compiles.steady_state" not in gauges, \
        "an unmarked run must not fake a zero"


def test_regress_pins_zero_steady_state_recompiles():
    from rocm_mpi_tpu.telemetry import regress

    base = {"gauges": {"compiles.steady_state": 0, "run.gpts": 2.0}}
    clean = {"gauges": {"compiles.steady_state": 0, "run.gpts": 2.1}}
    stormy = {"gauges": {"compiles.steady_state": 4, "run.gpts": 2.1}}
    assert not regress.regressions(regress.compare(clean, base))
    bad = regress.regressions(regress.compare(stormy, base))
    assert [d.name for d in bad] == ["gauges.compiles.steady_state"]
    # direction pins: a compile count going DOWN never regresses
    fewer = {"gauges": {"compiles.steady_state": 0, "compiles.total": 2}}
    more = {"gauges": {"compiles.steady_state": 0, "compiles.total": 9}}
    assert not regress.regressions(regress.compare(fewer, more))
    assert regress.regressions(regress.compare(more, fewer))


def test_compiles_install_smoke():
    """The real hook on the installed jax: a fresh jit compile is
    counted with its program name (mode 'named' on this pin; 'events'
    would still count, nameless)."""
    mode = compiles.install()
    if mode is None:
        pytest.skip("no compile listener available on this jax")
    import jax
    import jax.numpy as jnp

    before = compiles.snapshot()["totals"]["backend_compiles"]

    def never_seen_before_fn(x):
        return x * 3.0 + 1.5

    jax.jit(never_seen_before_fn)(jnp.arange(7.0)).block_until_ready()
    snap = compiles.snapshot()
    assert snap["totals"]["backend_compiles"] >= before + 1
    if mode == "named":
        assert any("never_seen_before_fn" in name
                   for name in snap["programs"]), snap["programs"]


# ---------------------------------------------------------------------------
# Acceptance drills: 2-rank weak_scaling via spawn_ranks (CPU/gloo)
# ---------------------------------------------------------------------------


def _spawn_health_run(tmp_path, inject=None, **kw):
    from rocm_mpi_tpu.parallel.launcher import spawn_ranks

    tel = tmp_path / "tel"
    return tel, spawn_ranks(
        [
            REPO / "apps" / "weak_scaling.py",
            "--cpu-devices", "1", "--local", "16", "--nt", "24",
            "--warmup", "4", "--counts", "2", "--dtype", "f32",
            "--telemetry-windows", "4", "--no-probes",
        ],
        nprocs=2,
        timeout=240,
        inject_fault=inject,
        telemetry_dir=tel,
        health_dir=tel,
        **kw,
    )


def test_watchdog_drill_stall_fault_names_rank1_by_progress(tmp_path):
    """THE acceptance drill: rank 1 wedges in a `stall` fault at a
    window boundary (scan-driver boundaries, q = gcd(4, 20) = 4:
    4, 12, 16, 20); the
    watchdog must name it by PROGRESS — its published step counter
    behind the advancing cross-rank median — dump a faulthandler
    traceback via SIGUSR2, write postmortem-rank1.json whose flight
    ring ends in a halo span, kill it, reap rank 0 with the existing
    peer grace, and bundle a merged timeline naming rank 1."""
    tel, results = _spawn_health_run(
        tmp_path, inject="stall@step=16,rank=1",
        heartbeat_s=2.0, peer_grace_s=6.0, stall_grace_s=3.0,
    )
    report = results.report
    # the watchdog — not the launch timeout — ended both ranks
    assert len(report.watchdog_verdicts) == 1, report.events
    verdict = report.watchdog_verdicts[0]
    assert verdict["rank"] == 1
    # detection is by progress: rank 1 never published boundary 16,
    # rank 0 did — so the median sits strictly ahead of the victim
    assert verdict["step"] == 12
    assert verdict["median_step"] > verdict["step"]
    assert verdict["last_phase"] == "halo"
    (p0, (_, _)), (p1, (out1, _)) = results
    assert p0.returncode != 0, "rank 0 was wedged and peer-grace killed"
    assert p1.returncode != 0, "rank 1 was killed by the watchdog"
    assert report.first_failure is not None and report.first_failure[0] == 1
    assert report.killed_after_failure == [0], \
        "the EXISTING peer-grace kill reaped the wedged survivor"
    # the health heartbeat line replaced the legacy wall-clock-only line
    assert any("last progress age" in e for e in report.events)
    # post-mortem: faulthandler traceback + flight ring ending in halo
    pm = json.loads((tel / "postmortem-rank1.json").read_text())
    assert pm["schema"] == flight.POSTMORTEM_SCHEMA
    assert "fault_point" in pm["traceback"], \
        "the all-thread dump must show where rank 1 is wedged"
    hb = pm["heartbeat"]
    assert hb["counters"]["step"] == 12
    assert hb["last_phase"] == "halo"
    span_like = [r for r in hb["ring"] if r["kind"] in ("span", "phase")]
    assert span_like and span_like[-1]["name"] == "halo.heartbeat", \
        "the ring's last phase is a halo span"
    # the merged bundle names rank 1 as the verdict
    bundle = json.loads((tel / "postmortem" / "bundle.json").read_text())
    assert [v["rank"] for v in bundle["verdicts"]] == [1]
    tl = json.loads((tel / "postmortem" / "timeline-trace.json").read_text())
    assert any(e["name"] == "watchdog.verdict" and e["pid"] == 1
               for e in tl["traceEvents"])
    ts = [e["ts"] for e in tl["traceEvents"] if e["ph"] != "M"]
    assert ts == sorted(ts)


def test_clean_health_run_zero_verdicts_and_zero_recompiles(tmp_path,
                                                            capsys):
    """The clean twin: same launch, no fault — zero watchdog verdicts,
    no postmortem/ bundle, and compiles.steady_state == 0 after warmup,
    pinned through `telemetry regress` against a zero baseline."""
    tel, results = _spawn_health_run(
        tmp_path, heartbeat_s=2.0, stall_grace_s=3.0,
    )
    for i, (p, (out, err)) in enumerate(results):
        assert p.returncode == 0, f"rank {i} rc={p.returncode}:\n{err}"
    assert results.report.watchdog_verdicts == []
    assert not (tel / "postmortem").is_dir(), \
        "a clean run must not leave an (empty) incident bundle"
    summary = json.loads((tel / "telemetry-summary.json").read_text())
    assert summary["gauges"]["compiles.steady_state"] == 0
    assert summary["gauges"]["compiles.total"] > 0
    # the halo heartbeat probes ran per window boundary on both ranks
    assert summary["phases"]["halo"]["count"] >= 8
    assert summary["traced"], "trace-time annotations intact"
    # regress-pinned: the summary gates against itself (zero baseline
    # zero current), and a doctored recompile storm fails the gate
    summary_path = tel / "telemetry-summary.json"
    assert cli_main(["regress", str(summary_path),
                     "--baseline", str(summary_path)]) == 0
    stormy = json.loads(summary_path.read_text())
    stormy["gauges"]["compiles.steady_state"] = 7
    stormy_path = tmp_path / "stormy.json"
    stormy_path.write_text(json.dumps(stormy))
    assert cli_main(["regress", str(stormy_path),
                     "--baseline", str(summary_path)]) == 1
    capsys.readouterr()
    # the sidecars the run left behind pass the schema gate lint.sh runs
    sidecars = sorted(str(p) for p in tel.glob("heartbeat-rank*.json"))
    assert len(sidecars) == 2
    assert cli_main(["regress", "--check-schema", *sidecars]) == 0
    capsys.readouterr()


def test_flight_enable_arms_collection(tmp_path):
    """Health without telemetry would flush structurally-valid but empty
    sidecars (last_phase null, ring []) — so arming the recorder arms
    the span/event stream too, into the same directory."""
    assert not events.enabled()
    flight.enable(directory=tmp_path, rank=0)
    assert events.enabled(), "--health implies collection"
    with telemetry.span("halo.x", phase="halo"):
        pass
    doc = flight.snapshot()
    assert doc["last_phase"] == "halo" and doc["ring"]


def test_spawn_ranks_clears_stale_sidecars_from_reused_health_dir(tmp_path):
    """A reused health_dir must not feed the watchdog last run's
    counters: fresh ranks spend longer than the stall grace in startup
    before their first flush, and stale uneven steps would get a healthy
    rank flagged and killed for the previous incident."""
    from rocm_mpi_tpu.parallel.launcher import spawn_ranks

    (tmp_path / "heartbeat-rank0.json").write_text(
        json.dumps(_beat(0, 2, phase="halo"))
    )
    (tmp_path / "heartbeat-rank1.json").write_text(json.dumps(_beat(1, 50)))
    (tmp_path / "postmortem-rank0.json").write_text(json.dumps(
        {"schema": flight.POSTMORTEM_SCHEMA, "v": 1, "rank": 0}
    ))
    (tmp_path / "postmortem-rank0.traceback").write_text("old dump")
    (tmp_path / "postmortem").mkdir()
    (tmp_path / "postmortem" / "bundle.json").write_text(json.dumps(
        {"schema": flight.BUNDLE_SCHEMA, "v": 1, "verdicts": [{"rank": 0}]}
    ))
    results = spawn_ranks(
        ["-c", "import time; time.sleep(6); print('ok')"],
        nprocs=2, timeout=60, health_dir=tmp_path, stall_grace_s=2.0,
    )
    assert all(p.returncode == 0 for p, _ in results)
    assert results.report.watchdog_verdicts == [], results.report.events
    assert not (tmp_path / "heartbeat-rank0.json").exists()
    assert not (tmp_path / "postmortem-rank0.json").exists()
    assert not (tmp_path / "postmortem-rank0.traceback").exists()
    assert not (tmp_path / "postmortem").exists(), \
        "clean reruns leave no bundle — last incident's dir is cleared"


# ---------------------------------------------------------------------------
# stall fault parsing (satellite 2)
# ---------------------------------------------------------------------------


def test_stall_fault_spec_parses_and_requires_trigger():
    from rocm_mpi_tpu.resilience import faults

    plan = faults.FaultPlan.parse("stall@step=14,rank=1")
    (clause,) = plan.clauses
    assert clause.kind == "stall" and clause.step == 14 and clause.rank == 1
    with pytest.raises(ValueError, match="step=K or segment=N"):
        faults.FaultPlan.parse("stall")

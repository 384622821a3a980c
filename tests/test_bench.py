"""bench.py: one process on the chip, no number without it.

Off the TPU these pin the refusal (exit 2, nothing on stdout); the
measurement's control flow is pinned in-process with a stub model.
"""

import json
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent


def _import_bench():
    """In-process bench import (shared by the unit-level tests)."""
    sys.path.insert(0, str(REPO))
    try:
        import bench
    finally:
        sys.path.pop(0)
    return bench


@pytest.mark.parametrize("args", [[], ["--suite"]])
def test_bench_without_chip_exits_nonzero_and_prints_no_rate(args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(REPO / "bench.py"), *args],
        capture_output=True, text=True, timeout=120, cwd=str(REPO), env=env,
    )
    assert proc.returncode == 2, proc.stderr[-1000:]
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_measure_kernel_form_ladder_picks_winner(monkeypatch, capsys):
    """Pin the stage-3 ladder's CONTROL FLOW in-process, so a crash there
    is found here and not on the chip: every candidate is timed as an EXPLICIT
    trace-time kwarg pair (never a mutated pk global — ADVICE r5 #1), the
    winner's kwargs are what the long window runs, the long-window emit
    is labeled with the winning form, and the best rate is what lands on
    stdout. Stub model; no accelerator needed."""
    bench = _import_bench()
    import rocm_mpi_tpu.ops.pallas_kernels as pk

    # Rates per (form, pad): conly+pad256 wins.
    rates = {
        ("eqc", False): 100.0,
        ("conly", False): 120.0,
        ("eqc", True): 110.0,
        ("conly", True): 150.0,
    }
    calls = []

    class _Res:
        def __init__(self, gpts):
            self.gpts = gpts
            self.wtime_it = 63504 / (gpts * 1e9)  # 252² points
            self.t_eff = gpts * 12.0

    class _Model:
        def __init__(self, nt, warmup):
            pass

        def run_vmem_resident(self, chunk=None, body_form=None,
                              pad_pow2=None, program_cache=None):
            # None defaults to the module constants, exactly as the real
            # fused_multi_step resolves them.
            form = pk.EQC_BODY_FORM if body_form is None else body_form
            pad = pk.VMEM_PAD_POW2 if pad_pow2 is None else pad_pow2
            calls.append((chunk, form, pad))
            if chunk == 16:  # the floor stage
                return _Res(50.0)
            return _Res(rates[(form, pad)])

    device = {"platform": "tpu", "kind": "stub", "count": 1}
    monkeypatch.setattr(bench, "_require_chip", lambda script: device)
    monkeypatch.setattr(bench, "_bench_model", lambda nt, wu: _Model(nt, wu))

    rc = bench.measure(budget_s=300.0)
    out = capsys.readouterr()
    assert rc == 0
    # The ladder passed every candidate explicitly and the module
    # constants were never touched (the measured hardware defaults).
    assert (pk.EQC_BODY_FORM, pk.VMEM_PAD_POW2) == ("eqc", False)
    assert {(f, p) for _, f, p in calls} == set(rates)
    # The long window (the last call) rides the winner's kwargs.
    assert calls[-1][1:] == ("conly", True)
    assert "kernel-form ladder winner: conly+pad256" in out.err
    assert "conly+pad256 x" in out.err  # long-window label carries the form
    # stdout's last emitted line is the best rate (the long window re-runs
    # the winner at the same stub rate, so 150.0 stands).
    last = json.loads(out.out.strip().splitlines()[-1])
    assert last["value"] == 150.0 and last["device"] == device


def test_ladder_program_cache_pins_reuse():
    """The kernel-form ladder satellite: identical configs across rungs
    must REUSE the compiled advance, not re-trace it per call — pinned by
    the compiles.total accounting (telemetry/compiles.py). Two same-config
    runs through one program_cache pay strictly fewer backend compiles
    than the same pair without it (the delta IS the re-traced advance;
    init_state's per-instance jits recompile either way, so the pin is a
    strict inequality, not an exact count)."""
    from rocm_mpi_tpu.config import DiffusionConfig
    from rocm_mpi_tpu.models import HeatDiffusion
    from rocm_mpi_tpu.telemetry import compiles

    def model():
        return HeatDiffusion(DiffusionConfig(
            global_shape=(16, 16), lengths=(10.0, 10.0), nt=8, warmup=4,
            dtype="f32", dims=(1, 1),
        ))

    mode = compiles.install()
    assert mode is not None, "compile listener must install on this jax"
    kw = dict(chunk=4, body_form="eqc", pad_pow2=False)

    def total():
        return compiles.snapshot()["totals"]["backend_compiles"]

    programs: dict = {}
    model().run_vmem_resident(program_cache=programs, **kw)  # warm trace
    t0 = total()
    model().run_vmem_resident(program_cache=programs, **kw)
    cached_delta = total() - t0
    assert len(programs) == 1  # one config -> one cached advance

    t1 = total()
    model().run_vmem_resident(**kw)  # no cache: the advance re-traces
    uncached_delta = total() - t1
    assert cached_delta < uncached_delta, (
        f"cached rerun compiled {cached_delta} programs vs "
        f"{uncached_delta} uncached — the ladder's program cache is not "
        "reusing traces"
    )


# ---------------------------------------------------------------------------
# Trajectory compare (ROADMAP item 5: bench.py --compare rN rM)
# ---------------------------------------------------------------------------


def _record(path, rows):
    path.write_text(json.dumps({
        "metrics": {
            k: {"value": v, "direction": "higher"} for k, v in rows.items()
        },
    }))
    return path


def test_compare_resolves_record_specs():
    bench = _import_bench()
    root = str(REPO)
    assert bench._resolve_record("r3") == os.path.join(
        root, "BENCH_r03.json")
    assert bench._resolve_record("r12") == os.path.join(
        root, "BENCH_r12.json")
    assert bench._resolve_record("7") == os.path.join(
        root, "BENCH_r07.json")
    # explicit paths pass through untouched (archived records)
    assert bench._resolve_record("docs/x/BENCH_r01.json") == \
        "docs/x/BENCH_r01.json"
    with pytest.raises(ValueError, match="--compare operand"):
        bench._resolve_record("rX")


def test_compare_reports_deltas_and_gates_regressions(tmp_path, capsys):
    """The trajectory report: per-key delta rows against the regress
    tolerance semantics — exit 0 within tolerance, exit 1 when a rung
    moved the wrong way, and dropped/new rungs named instead of
    silently vanishing from the diff."""
    bench = _import_bench()
    base = _record(tmp_path / "BENCH_r01.json",
                   {"suite.a.gpts": 10.0, "suite.b.gpts": 5.0,
                    "suite.old.gpts": 1.0})
    cur = _record(tmp_path / "BENCH_r02.json",
                  {"suite.a.gpts": 10.5, "suite.b.gpts": 5.2,
                   "suite.new.req_s": 7.0})
    assert bench.compare_records(str(base), str(cur)) == 0
    out = capsys.readouterr().out
    assert "suite.a.gpts" in out and "+5.0%" in out
    assert "dropped (baseline-only rung)" in out  # suite.old
    assert "new (no baseline)" in out  # suite.new
    assert "2 compared, 0 regressed, 1 dropped, 1 new" in out

    # a higher-is-better rung falling past the tolerance gates exit 1
    worse = _record(tmp_path / "BENCH_r03.json",
                    {"suite.a.gpts": 10.0, "suite.b.gpts": 2.0})
    assert bench.compare_records(str(base), str(worse)) == 1
    assert "REGRESSED" in capsys.readouterr().out
    # ... unless the caller widens the tolerance explicitly
    assert bench.compare_records(str(base), str(worse),
                                 tolerance=0.9) == 0
    capsys.readouterr()


def test_compare_rejects_unreadable_and_disjoint_inputs(tmp_path, capsys):
    bench = _import_bench()
    base = _record(tmp_path / "BENCH_r01.json", {"suite.a.gpts": 1.0})
    assert bench.compare_records(
        str(base), str(tmp_path / "missing.json")) == 2
    assert "cannot read" in capsys.readouterr().err
    other = _record(tmp_path / "BENCH_r04.json", {"suite.z.gpts": 1.0})
    assert bench.compare_records(str(base), str(other)) == 2
    assert "no shared metric keys" in capsys.readouterr().err


def test_compare_cli_end_to_end(tmp_path):
    """The CLI spelling the ROADMAP names: `bench.py --compare rN rM`
    (explicit paths here — the repo root's numbered records are the
    chip window's to bank) runs backend-free and fast."""
    base = _record(tmp_path / "BENCH_r01.json", {"suite.a.gpts": 4.0})
    cur = _record(tmp_path / "BENCH_r02.json", {"suite.a.gpts": 1.0})
    ok = subprocess.run(
        [sys.executable, str(REPO / "bench.py"),
         "--compare", str(cur), str(cur)],
        capture_output=True, text=True, timeout=60, cwd=str(REPO),
    )
    assert ok.returncode == 0, ok.stderr
    assert "0 regressed" in ok.stdout
    bad = subprocess.run(
        [sys.executable, str(REPO / "bench.py"),
         "--compare", str(base), str(cur)],
        capture_output=True, text=True, timeout=60, cwd=str(REPO),
    )
    assert bad.returncode == 1, (bad.stdout, bad.stderr)
    assert "REGRESSED" in bad.stdout
    usage = subprocess.run(
        [sys.executable, str(REPO / "bench.py"), "--compare", str(base)],
        capture_output=True, text=True, timeout=60, cwd=str(REPO),
    )
    assert usage.returncode == 2
    assert "usage" in usage.stderr


# ---------------------------------------------------------------------------
# Compile-cache placement (utils.backend.enable_persistent_cache: the one
# place a cache path is set, shared by bench.py and chip_smoke.py)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("env_dir", [True, False])
def test_cache_dir_follows_env_else_checkout(monkeypatch, tmp_path, env_dir):
    import jax

    from rocm_mpi_tpu.utils.backend import enable_persistent_cache

    saved = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("RMT_CPU_CACHE", "1")
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        want = str(tmp_path)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(REPO / ".jax_cache")
    try:
        enable_persistent_cache()
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", saved)

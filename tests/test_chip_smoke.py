"""chip_smoke.py: refuses without a chip; its phases run end to end.

The script itself only ever runs on the TPU. Off it, it must exit nonzero
and print no result. Its phases are plain functions, so the control flow
and the oracle checks are rehearsed here at tiny sizes on the virtual CPU
mesh, with Pallas in interpret mode.
"""

import os
import pathlib
import shutil
import subprocess
import sys

import jax
import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent


def _run(script, args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(script), *args], capture_output=True,
        text=True, timeout=120, cwd=str(cwd), env=env,
    )


@pytest.mark.parametrize("args", [[], ["--four-chips"]])
def test_refuses_without_tpu(args):
    proc = _run(REPO / "chip_smoke.py", args, REPO)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_refuses_outside_the_repo(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path)
    proc = _run(tmp_path / "chip_smoke.py", [], tmp_path)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


@pytest.fixture
def smoke(monkeypatch):
    sys.path.insert(0, str(REPO))
    try:
        import chip_smoke
    finally:
        sys.path.pop(0)
    from rocm_mpi_tpu.telemetry import compiles

    compiles.install()
    # The 2x2 phase checks every device holds a shard: show it four.
    four = jax.devices()[:4]
    monkeypatch.setattr(jax, "devices", lambda *a: four)
    return chip_smoke


@pytest.mark.parametrize("phase,args", [
    ("phase_perf", (64, 20, 4)),
    ("phase_tb", (64, 8, 24, 8)),
    ("phase_vmem", (16, 64, 16)),
    ("phase_serve", (32, 2, 20)),
    ("phase_four_chips", (32, 40, 4)),
])
def test_phase_agrees_with_oracle(smoke, phase, args):
    out = getattr(smoke, phase)(*args)
    lines = [out] if isinstance(out, str) else out
    assert lines and all("<= tol" in ln for ln in lines)


@pytest.mark.parametrize("regime", ["cpu", "chip"])
def test_check_fails_frozen_hide_slabs(smoke, regime):
    """Control: a 2x2 `hide` result whose boundary slabs stayed at T0
    must fail `_check`. "chip" rescales every increment from T0 so the
    oracle moves exactly the rounding bound, as it does at 12288² per
    chip; there the absolute bound alone passes the frozen result."""
    import jax.numpy as jnp
    import numpy as np

    from rocm_mpi_tpu.models import HeatDiffusion
    from rocm_mpi_tpu.parallel.overlap import effective_b_width

    nt = 40
    cfg, driver = smoke.config(64, nt, 4, dims=(2, 2))
    model = HeatDiffusion(cfg)
    got = np.asarray(model.run(variant="hide", driver=driver).T, np.float64)
    ref, T0 = (np.asarray(a, np.float64) for a in smoke._oracle(cfg, nt))
    frozen = got.copy()
    local = model.grid.local_shape
    bw = effective_b_width(local, cfg.b_width)
    for i in range(2):
        for j in range(2):
            blk = (slice(i * local[0], (i + 1) * local[0]),
                   slice(j * local[1], (j + 1) * local[1]))
            core = np.zeros(local, bool)
            core[bw[0]:-bw[0], bw[1]:-bw[1]] = True
            frozen[blk] = np.where(core, got[blk], T0[blk])
    tol = 2.0 * nt * smoke.EPS32 * np.abs(T0).max()
    if regime == "chip":
        s = tol / np.abs(ref - T0).max()
        got, frozen, ref = (T0 + s * (a - T0) for a in (got, frozen, ref))
        assert np.abs(frozen - ref).max() <= tol
    smoke._check("hide", jnp.asarray(got), ref, T0, nt)
    with pytest.raises(AssertionError):
        smoke._check("hide", jnp.asarray(frozen), ref, T0, nt)

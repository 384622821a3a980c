"""Test harness: simulate an 8-device TPU mesh on CPU.

The reference assumes a Slurm cluster and has no way to test multi-rank
behavior locally (SURVEY.md §4.5). The TPU-native answer is XLA's virtual
host devices: force 8 CPU devices so every mesh/halo/collective test runs
single-process, no hardware needed. f64 is enabled to match the reference's
Float64 physics (diffusion_2D_ap.jl:22-26).

The platform is pinned through jax.config as well as the environment, so
the harness holds even when a plugin has already imported jax.

Two speed levers keep the per-commit gate fast without losing coverage:

* **soak lane** — tests marked `slow` (the wall-clock bench-robustness
  contracts, duplicate dryrun sizes, the heaviest subprocess app runs)
  are deselected by default and run with `--soak` (or RMT_SOAK=1). The
  lane is part of the round's acceptance: run it before shipping a round.
* **machine-local CPU compile cache** — RMT_CPU_CACHE=1 +
  JAX_COMPILATION_CACHE_DIR point this process AND every spawned child
  (apps, bench, dryrun subprocesses) at an untracked per-machine XLA
  cache, so re-runs skip identical XLA:CPU compiles. Safe precisely
  because the dir never leaves the machine that wrote it (the SIGILL
  feature-mismatch hazard needs a foreign cache); see utils.backend.
"""

import os
import pathlib
import shutil
import subprocess

import pytest

_REPO = pathlib.Path(__file__).resolve().parent.parent

os.environ["JAX_PLATFORMS"] = "cpu"  # for any subprocesses we spawn
os.environ.setdefault("RMT_CPU_CACHE", "1")  # =0 disables (utils.backend)
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR", str(_REPO / ".jax_cache_cpu")
)


def _env_on(name: str) -> bool:
    """Value-aware env flag: '0'/''/'false'/'no' mean OFF, not presence."""
    return os.environ.get(name, "").strip().lower() not in (
        "", "0", "false", "no",
    )

import jax  # noqa: E402


def pytest_addoption(parser):
    parser.addoption(
        "--soak", action="store_true", default=False,
        help="also run the slow-marked soak/robustness lane",
    )


def pytest_configure(config):
    """Register the soak marker and build the native host-staging engine
    before collection when a toolchain is present, so a fresh checkout
    runs the full test matrix instead of silently skipping the
    native-vs-numpy bit-identity tests (the reference's startup.sh
    likewise builds before first run, /root/reference/startup.sh:5-17).
    Failure is non-fatal: the native tests then skip with their usual
    instructions."""
    config.addinivalue_line(
        "markers",
        "slow: soak/robustness lane — deselected by default; run with "
        "--soak or RMT_SOAK=1",
    )
    if shutil.which("g++") is None or shutil.which("make") is None:
        return
    subprocess.run(
        ["make", "-C", str(_REPO / "native")],
        check=False, capture_output=True,
    )


def pytest_collection_modifyitems(config, items):
    if config.getoption("--soak") or _env_on("RMT_SOAK"):
        return
    skip = pytest.mark.skip(
        reason="soak lane: pass --soak (or RMT_SOAK=1) to run"
    )
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


from rocm_mpi_tpu.utils.backend import set_cpu_device_count  # noqa: E402

jax.config.update("jax_platforms", "cpu")
set_cpu_device_count(8)
jax.config.update("jax_enable_x64", True)

assert len(jax.devices()) == 8, (
    "test harness requires 8 virtual CPU devices, got "
    f"{jax.devices()} — was a backend initialized before conftest ran?"
)

# In-process compile cache too: the suite's own jit programs (the virtual
# 8-device mesh tests) persist across runs of the per-commit gate.
from rocm_mpi_tpu.utils.backend import enable_persistent_cache  # noqa: E402

enable_persistent_cache()

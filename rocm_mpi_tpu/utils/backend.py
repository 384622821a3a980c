"""Backend selection, the virtual-CPU-device knob and the persistent
compilation cache: the process-level JAX settings every entry point
(chip_smoke.py, bench.py, the apps, scripts/bench_*.py, tests/conftest.py)
routes through, so each is encoded exactly once.
"""

from __future__ import annotations

import os


def enable_persistent_cache() -> None:
    """Point this process at the persistent XLA compilation cache:
    JAX_COMPILATION_CACHE_DIR when set, else <checkout>/.jax_cache. No
    other code sets a cache path.

    Off on the CPU backend unless RMT_CPU_CACHE=1, the test harness's
    machine-local opt-in (tests/conftest.py): XLA:CPU entries embed the
    compiling machine's CPU feature set and can SIGILL elsewhere, but
    the harness's directory never leaves the machine that wrote it, and
    the suite's subprocess children stop re-paying identical compiles.
    """
    import jax

    cpu_cache = os.environ.get("RMT_CPU_CACHE", "").strip().lower() not in (
        "", "0", "false", "no",
    )
    if jax.default_backend() == "cpu" and not cpu_cache:
        return
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
        ".jax_cache",
    )
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def set_cpu_device_count(n: int) -> None:
    """Request `n` virtual CPU devices (`jax_num_cpu_devices`). Must run
    before the CPU backend initializes; jax raises afterwards."""
    import jax

    n = int(n)
    if n < 1:
        raise ValueError(f"device count must be >= 1, got {n}")
    jax.config.update("jax_num_cpu_devices", n)


def require_accelerator(script: str) -> dict:
    """The TPU as JAX reports it ({"platform", "kind", "count"}); exit 2
    on any other backend. Measurement entry points call this so a run
    without the chip produces no number at all — never an interpret-mode
    or CPU timing under a device metric's name."""
    import sys

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(
            f"{script}: no TPU (jax.devices()[0].platform="
            f"{devs[0].platform!r}) — refusing to measure",
            file=sys.stderr,
            flush=True,
        )
        raise SystemExit(2)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


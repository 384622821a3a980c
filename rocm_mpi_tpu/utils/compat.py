"""JAX API chokepoint for the installed jax (0.9.0).

One module owns every "where does this live / what is it called in this
jax" question, so the next version bump is fixed in exactly one place
(graftlint GL03 forbids the raw spellings anywhere else):

* `shard_map`, `axis_size`, `cost_analysis_dict`, `out_struct_like` —
  the modern-API spellings the call sites use;
* `install_compile_listener` — the private dispatch hook the compile
  tracker taps;
* the jax.experimental modules the framework uses (`pallas`, its `tpu`
  sublayer, `multihost_utils`), resolved lazily via module `__getattr__`
  (PEP 562) so importing compat for shard_map alone does not pay the
  Pallas import. This module's `__all__` is the one allowlist a version
  bump has to revisit.

`utils.backend.set_cpu_device_count` is the same idea for the
virtual-CPU-device knob.
"""

from __future__ import annotations

from jax import shard_map

__all__ = [
    "axis_size",
    "cost_analysis_dict",
    "install_compile_listener",
    "multihost_utils",
    "out_struct_like",
    "pallas",
    "pallas_tpu",
    "shard_map",
]


def axis_size(axis_name) -> int:
    """`lax.axis_size`: the mesh axis size as a Python int at trace time,
    usable to build ppermute permutations."""
    from jax import lax

    return lax.axis_size(axis_name)


def cost_analysis_dict(compiled) -> dict:
    """`compiled.cost_analysis()` as a dict ({} when unavailable)."""
    return dict(compiled.cost_analysis() or {})


BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

_COMPILE_LISTENER_MODE: str | None = None


def install_compile_listener(on_interval, on_event=None) -> str | None:
    """Tap jax's compile pipeline for the compile tracker
    (telemetry/compiles.py) by wrapping `jax._src.dispatch.log_elapsed_time`,
    the context manager every trace/lower/backend-compile interval runs
    under — its `fun_name` is the per-program identity the public
    monitoring API does not carry. Call sites resolve it as a module
    attribute at call time, so the install-once rebind below is effective
    without reimporting anything.

    `on_interval(event, name, dur_s)` receives every completed interval
    (event is e.g. BACKEND_COMPILE_EVENT); `on_event(event)` receives
    point events (persistent-cache hit/miss). Both are wrapped so a
    listener exception can never break a compile. Installs at most once
    per process; returns the mode ("named").
    """
    global _COMPILE_LISTENER_MODE
    if _COMPILE_LISTENER_MODE is not None:
        return _COMPILE_LISTENER_MODE
    import contextlib
    import time

    def _safe_interval(event, name, dur_s):
        try:
            on_interval(event, name, dur_s)
        except Exception:  # noqa: BLE001 — never break a compile
            pass

    from jax._src import dispatch as _dispatch

    _orig = _dispatch.log_elapsed_time

    @contextlib.contextmanager
    def _tapped_log_elapsed_time(*args, **kwargs):
        # Signature-transparent: the installed jax calls
        # (fmt, fun_name=…, event=…); a parameter a later jax adds or
        # renames must cost ATTRIBUTION, not the run — a TypeError here
        # would propagate out of every jit trace.
        fun_name = kwargs.get("fun_name")
        event = kwargs.get("event")
        if len(args) > 1 and fun_name is None:
            fun_name = args[1]
        if len(args) > 2 and event is None:
            event = args[2]
        t0 = time.monotonic()
        with _orig(*args, **kwargs):
            yield
        # Only a COMPLETED interval counts (an aborted compile is an
        # error, not a compile); jax's own listeners already fired
        # inside _orig's exit.
        _safe_interval(event, fun_name, time.monotonic() - t0)

    # The install-once seam this function exists for — not a trace-time
    # knob (GL02's hazard); cached programs are unaffected, only future
    # compiles pass through the tap.
    _dispatch.log_elapsed_time = _tapped_log_elapsed_time
    if on_event is not None:
        import jax.monitoring

        def _safe_event(event, **kw):
            try:
                on_event(event)
            except Exception:  # noqa: BLE001
                pass

        jax.monitoring.register_event_listener(_safe_event)
    _COMPILE_LISTENER_MODE = "named"
    return _COMPILE_LISTENER_MODE


def _resolve_lazy(name: str):
    """The jax.experimental residents, resolved on first attribute access."""
    if name == "pallas":
        from jax.experimental import pallas

        return pallas
    if name == "pallas_tpu":
        from jax.experimental.pallas import tpu

        return tpu
    if name == "multihost_utils":
        from jax.experimental import multihost_utils

        return multihost_utils
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __getattr__(name: str):  # PEP 562: lazy jax.experimental resolution
    value = _resolve_lazy(name)
    globals()[name] = value  # cache: resolve once per process
    return value


def out_struct_like(shape, exemplar):
    """ShapeDtypeStruct matching `exemplar`'s dtype and mesh-varying axes:
    under check_vma, pallas_call outputs inside shard_map must declare
    which mesh axes they vary over, so propagate the input's vma set."""
    import jax

    return jax.ShapeDtypeStruct(
        shape, exemplar.dtype, vma=jax.typeof(exemplar).vma
    )

"""The plain reference of the serving configuration: one request's
initial field and its update, from the request's own `ic_scale` and
`nt` alone. It imports nothing of the program. A configuration names it
by its `reference` key.

A request's initial field is the service's standard one, the
reference's centred Gaussian (diffusion_2D_ap.jl:28, cell centres),
times the request's `ic_scale`, with Cp = cp0 on every cell: computed
in float64 on the host and rounded once to the configuration's dtype.
The update is the shared plain step of the diffusion configurations
(`references/diffusion2d.py`: the staggered flux form, edges held, the
reference's dt rule), run for the request's `nt` steps under
`jax.default_matmul_precision("highest")`.
"""

from __future__ import annotations

import functools

import numpy as np

from benchmark.references import diffusion2d

DTYPES = {"f32": "float32", "f64": "float64"}


@functools.lru_cache(maxsize=None)
def _gaussian(shape: tuple, lengths: tuple) -> np.ndarray:
    r2 = 0.0
    for ax, (n, ln) in enumerate(zip(shape, lengths)):
        c = (np.arange(n, dtype=np.float64) + 0.5) * (ln / n) - ln / 2
        r2 = r2 + np.expand_dims(
            c * c, [a for a in range(len(shape)) if a != ax])
    return np.exp(-r2)


def initial_field(config: dict, ic_scale: float, dtype=None) -> np.ndarray:
    """A request's initial field: the standard Gaussian times
    `ic_scale`, as a host array of `dtype` (default: the
    configuration's)."""
    g = _gaussian(tuple(config["global_shape"]), tuple(config["lengths"]))
    return (g * float(ic_scale)).astype(dtype or DTYPES[config["dtype"]])


def advance(config: dict, T, nt: int, dtype):
    """T after `nt` reference steps, computed in `dtype` with Cp = cp0,
    returned in `dtype` on the device."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype)
    cp = jnp.full(tuple(config["global_shape"]), config["cp0"], dtype)
    with jax.default_matmul_precision("highest"):
        return diffusion2d.advance_fn(config, int(nt), dtype)(
            jnp.asarray(T, dtype), cp)

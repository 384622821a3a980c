"""The plain reference of the 2D shallow-water configurations: their
initial state made from `--seed`, and the linear shallow-water update on
an Arakawa C-grid with forward-backward time stepping (Mesinger &
Arakawa 1976, GARP Publ. Series 17) in straightforward jax.numpy. It
imports nothing of the program and takes only the fields and the
configuration. A configuration names it by its `reference` key.

The grid: h at cell centres, u at the +x face and v at the +y face of
each cell, all three arrays of the grid's own shape. The closed basin's
walls are the face masks Mu, Mv: exactly 0 on the high wall face of
their axis (the last row of u, the last column of v), 1 elsewhere; the
low wall is the face before the first cell, whose velocity is 0. One
step, with every value beyond the domain read as 0:

    h' = h - dt·H·((u[i] - u[i-1]) / dx + (v[j] - v[j-1]) / dy)
    u' = Mu · (u - dt·g·(h'[i+1] - h'[i]) / dx)
    v' = Mv · (v - dt·g·(h'[j+1] - h'[j]) / dy)

with dt = cfl·min(dx, dy) / (sqrt(g·H)·sqrt(2)). The velocities take the
gradient of the updated h: that is the forward-backward pairing.

The state (make_state), made on the device in one jitted call with the
grid's sharding: h is a centred Gaussian bump (cell centres) plus
`noise` times uniform noise in [0, 1) on every cell; u and v are
`velocity_noise` times uniform noise in [-0.5, 0.5), times their masks,
so the wall faces start at exactly 0. Noise makes every cell move by far
more than its rounding for the whole window. Every seed gives the same
shapes: the seed changes values only.
"""

from __future__ import annotations

import functools
import math

from benchmark.seeds import seed_key


def make_state(seed: int, config: dict, inputs: dict, sharding):
    """((h, (u, v)), (Mu, Mv)) as f32 device arrays of
    config["global_shape"], placed with `sharding`; `inputs` is the
    traffic file's parameters."""
    return _maker(
        tuple(config["global_shape"]), tuple(config["lengths"]),
        float(inputs["noise"]), float(inputs["velocity_noise"]), sharding,
    )(seed_key(seed))


def face_masks(shape, dtype):
    """(Mu, Mv): 0 on the high wall face of each axis, 1 elsewhere."""
    import jax.numpy as jnp
    from jax import lax

    return tuple(
        jnp.where(lax.broadcasted_iota(jnp.int32, shape, a) == n - 1,
                  jnp.zeros(shape, dtype), jnp.ones(shape, dtype))
        for a, n in enumerate(shape))


@functools.lru_cache(maxsize=None)
def _maker(shape, lengths, noise, velocity_noise, sharding):
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    state_sharding = (sharding, (sharding, sharding))

    @functools.partial(jax.jit,
                       out_shardings=(state_sharding, (sharding, sharding)))
    def make(key):
        k_h, k_u, k_v = jax.random.split(key, 3)
        r2 = 0.0
        for ax, (n, ln) in enumerate(zip(shape, lengths)):
            c = (jnp.arange(n, dtype=f32) + 0.5) * f32(ln / n) - f32(ln / 2)
            r2 = r2 + jnp.expand_dims(
                c * c, [a for a in range(len(shape)) if a != ax])
        h = jnp.exp(-r2) + f32(noise) * jax.random.uniform(k_h, shape, f32)
        Mu, Mv = face_masks(shape, f32)
        u = Mu * f32(velocity_noise) * (
            jax.random.uniform(k_u, shape, f32) - 0.5)
        v = Mv * f32(velocity_noise) * (
            jax.random.uniform(k_v, shape, f32) - 0.5)
        return (h, (u, v)), (Mu, Mv)

    return make


def geometry(config: dict) -> tuple[tuple[float, float], float]:
    """((dx, dy), dt) of the configuration, in float64 on the host."""
    (nx, ny), (lx, ly) = config["global_shape"], config["lengths"]
    dx, dy = lx / nx, ly / ny
    c = math.sqrt(config["g"] * config["H0"])
    dt = config["cfl"] * min(dx, dy) / (c * math.sqrt(2))
    return (dx, dy), dt


def advance_fn(config: dict, steps: int, dtype):
    """jitted ((h, (u, v)), (Mu, Mv)) -> (h, (u, v)) after `steps`
    reference steps, computed in `dtype` (the state, the masks and every
    constant), returned in `dtype`."""
    import jax.numpy as jnp

    (dx, dy), dt = geometry(config)
    return _advance(steps, float(config["H0"]), float(config["g"]), dx, dy,
                    dt, jnp.dtype(dtype).name)


@functools.lru_cache(maxsize=None)
def _advance(steps, H, g, dx, dy, dt, dtype_name):
    import jax
    import jax.numpy as jnp
    from jax import lax

    dtype = jnp.dtype(dtype_name)
    H, g, dx, dy, dt = (dtype.type(v) for v in (H, g, dx, dy, dt))

    def before(a, axis):
        """a[i-1] along `axis`, 0 before the first cell."""
        pad = [(0, 0), (0, 0)]
        pad[axis] = (1, 0)
        return lax.slice_in_dim(jnp.pad(a, pad), 0, a.shape[axis], 1, axis)

    def after(a, axis):
        """a[i+1] along `axis`, 0 past the last cell."""
        pad = [(0, 0), (0, 0)]
        pad[axis] = (0, 1)
        return lax.slice_in_dim(jnp.pad(a, pad), 1, a.shape[axis] + 1, 1,
                                axis)

    def step(state, masks):
        h, (u, v) = state
        Mu, Mv = masks
        h = h - dt * H * ((u - before(u, 0)) / dx + (v - before(v, 1)) / dy)
        u = Mu * (u - dt * g * (after(h, 0) - h) / dx)
        v = Mv * (v - dt * g * (after(h, 1) - h) / dy)
        return h, (u, v)

    @jax.jit
    def advance(state, masks):
        with jax.default_matmul_precision("highest"):
            state = jax.tree.map(lambda a: a.astype(dtype), state)
            masks = jax.tree.map(lambda a: a.astype(dtype), masks)
            return lax.fori_loop(0, steps, lambda _, s: step(s, masks),
                                 state)

    return advance

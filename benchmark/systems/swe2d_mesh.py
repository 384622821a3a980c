"""The system under test of the sharded 2D shallow-water configurations:
systems/swe2d.py's program (the app's parser and `build_config`, then
`ShallowWater` and its scan driver), built once the configuration's
`b_width` is checked against the app's, which has no flag for it. A
configuration names it by its `system` key.

FAULTS are systems/swe2d.py's, and one that breaks what exists only
across chips: each is fault(program, config, traffic, patch) -> program,
as there; no benchmark run uses them.
"""

from __future__ import annotations

from benchmark.systems import swe2d


def build(config: dict, traffic: dict, devices) -> swe2d.Program:
    """systems/swe2d.py's program, for a configuration whose `b_width`
    is the one the app builds."""
    from apps.swe_2d import build_config, make_parser

    built = tuple(build_config(make_parser().parse_args([])).b_width)
    if built != tuple(config["b_width"]):
        raise ValueError(f"the app builds b_width {built}, the "
                         f"configuration states {config['b_width']}")
    return swe2d.build(config, traffic, devices)


def ghosts_zero(prog, config, traffic, patch):
    """The halo exchange is skipped: every slab reads zero ghosts, as if
    each shard were a closed basin of its own."""
    import jax
    import jax.numpy as jnp

    from rocm_mpi_tpu.parallel import overlap

    real = overlap.exchange_slabs
    patch(overlap, "exchange_slabs", lambda u, grid, **kw: jax.tree.map(
        jnp.zeros_like, real(u, grid, **kw)))
    return prog


FAULTS = {**swe2d.FAULTS, "ghosts_zero": ghosts_zero}

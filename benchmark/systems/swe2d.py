"""The system under test of the 2D shallow-water configurations, built
the way the app builds it: apps/swe_2d.py's own parser and
`build_config`, then `ShallowWater` and its scan driver, for the variant
the traffic names. A configuration names it by its `system` key.
Nothing else of the program is used by a benchmark run.

FAULTS break the built program for the proofs that `correct` fails
(benchmark/readings.py on the chip, benchmark/tests on the CPU); no
benchmark run uses them. Each is fault(program, config, traffic, patch)
-> program, as in systems/diffusion2d.py.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class Program:
    advance: object  # ((h, (u, v)), (Mu, Mv), n_steps) -> state; donates it
    sharding: object  # the grid's sharding of every field and mask


def build(config: dict, traffic: dict, devices) -> Program:
    """The app's program for `traffic["variant"]` on `devices`, in the
    app's default loop form, the scan driver."""
    from apps.swe_2d import build_config, make_parser
    from rocm_mpi_tpu.models.swe import ShallowWater

    nx, ny = config["global_shape"]
    args = make_parser().parse_args([
        "--nx", str(nx), "--ny", str(ny), "--nt", str(config["nt"]),
        "--warmup", str(config["warmup"]), "--dtype", config["dtype"],
        "--dims", ",".join(str(d) for d in config["dims"])])
    cfg = build_config(args)
    built = (cfg.H0, cfg.g, cfg.cfl, list(cfg.lengths))
    stated = (config["H0"], config["g"], config["cfl"], config["lengths"])
    if built != stated:
        raise ValueError(f"the app builds (H0, g, cfl, lengths) = {built}, "
                         f"the configuration states {stated}")
    model = ShallowWater(cfg, devices=devices)
    advance, _ = model.scan_advance_fn(traffic["variant"])
    return Program(advance=lambda s, masks, n: advance(*s, masks, n),
                   sharding=model.grid.sharding)


# ---- faults: the timed path broken underneath ---------------------------


def unchanged(prog, config, traffic, patch):
    """Each call returns its state unchanged."""
    prog.advance = lambda s, masks, n: s
    return prog


def velocities_held(prog, config, traffic, patch):
    """u and v are not updated: each call returns the velocities it was
    given beside the h it computed."""
    import jax
    import jax.numpy as jnp

    copy = jax.jit(lambda us: jax.tree.map(jnp.copy, us))
    adv = prog.advance

    def advance(s, masks, n):
        held = copy(s[1])
        return adv(s, masks, n)[0], held

    prog.advance = advance
    return prog


def walls_open(prog, config, traffic, patch):
    """Every face mask is ones: the walls let water through."""
    import jax.numpy as jnp

    adv = prog.advance
    prog.advance = lambda s, masks, n: adv(
        s, tuple(jnp.ones_like(m) for m in masks), n)
    return prog


def swapped(prog, config, traffic, patch):
    """u and v trade places after each call."""
    adv = prog.advance

    def advance(s, masks, n):
        h, (u, v) = adv(s, masks, n)
        return h, (v, u)

    prog.advance = advance
    return prog


FAULTS = {f.__name__: f
          for f in (unchanged, velocities_held, walls_open, swapped)}

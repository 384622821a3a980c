"""The system under test of the serving configuration: the multi-tenant
`SimulationService`, built the way `apps/serve.py --serve` builds it,
from the app's own parser defaults. A configuration names it by its
`system` key. The requests it serves are the configuration's field
(shape, dtype) with the service's standard physics; each request's own
`nt` and `ic_scale` come from the traffic.

FAULTS break the built program for the proofs that `correct` fails
(benchmark/readings.py on the chip, the tests on the CPU); no benchmark
run uses them. Each is fault(program, config, traffic, patch) ->
program, where patch(obj, name, value) sets an attribute for the rest of
the process (a test passes monkeypatch.setattr).
"""

from __future__ import annotations

import dataclasses
from typing import Callable


@dataclasses.dataclass
class Program:
    new_service: Callable  # () -> a fresh SimulationService
    request: Callable  # (request_id, nt, ic_scale) -> Request


def build(config: dict, traffic: dict, devices) -> Program:
    """The service `apps/serve.py --serve` builds with no other flag,
    for requests of the configuration's field. The service plans its
    mesh over every device the process sees, as the app does; the cell
    gives the process one chip."""
    from apps.serve import make_parser
    from rocm_mpi_tpu.serving import bins
    from rocm_mpi_tpu.serving.queue import Request
    from rocm_mpi_tpu.serving.service import (
        _ADAPTERS, ServeConfig, SimulationService)

    args = make_parser().parse_args(["--serve"])
    kw = {}
    if args.pipeline_depth is not None:
        kw["pipeline_depth"] = args.pipeline_depth

    def new_service():
        return SimulationService(config=ServeConfig(
            max_width=args.max_width,
            occupancy_floor=args.occupancy_floor,
            batch_dims=args.batch_dims,
            grow_queue_depth=args.grow_depth,
            max_depth=args.max_depth,
            **kw,
        ))

    shape = tuple(config["global_shape"])

    def request(request_id: str, nt: int, ic_scale: float):
        return Request(request_id=request_id, workload="diffusion",
                       global_shape=shape, dtype=config["dtype"],
                       nt=int(nt), ic_scale=float(ic_scale))

    key = bins.bin_key(request("probe", 1, 1.0))
    cfg = _ADAPTERS[key.workload].make_config(key, tuple(config["dims"]))
    if (cfg.lam, cfg.cp0, tuple(cfg.lengths)) != (
        config["lam"], config["cp0"], tuple(config["lengths"])
    ):
        raise ValueError(
            f"the service builds lam={cfg.lam} cp0={cfg.cp0} lengths="
            f"{cfg.lengths}, the configuration states {config['lam']}, "
            f"{config['cp0']}, {config['lengths']}"
        )
    return Program(new_service=new_service, request=request)


# ---- faults: the served path broken underneath ---------------------------


def _adapter():
    from rocm_mpi_tpu.serving.service import _ADAPTERS

    return _ADAPTERS["diffusion"]


def unchanged(prog, config, traffic, patch):
    """Each ticket gets its initial field: the batch is never advanced."""
    patch(_adapter(), "run", lambda p, leaves, steps, n: tuple(leaves))
    return prog


def overrun(prog, config, traffic, patch):
    """The per-lane freeze left out: every lane runs the batch's n
    steps, not its own nt."""
    import jax
    import numpy as np

    ad = _adapter()
    real = ad.run

    def run(p, leaves, steps, n):
        full = jax.device_put(np.full(steps.shape, n, np.int32),
                              steps.sharding)
        return real(p, leaves, full, n)

    patch(ad, "run", run)
    return prog


def swapped(prog, config, traffic, patch):
    """In every batch of two lanes or more, the first and the last live
    lane's results go to each other's tickets."""
    from rocm_mpi_tpu.serving.service import SimulationService

    real = SimulationService._resolve_batch

    def resolve(self, fl):
        if len(fl.live) > 1:
            fl.live[0], fl.live[-1] = fl.live[-1], fl.live[0]
        return real(self, fl)

    patch(SimulationService, "_resolve_batch", resolve)
    return prog


def ring_frozen(prog, config, traffic, patch):
    """Every cell farther than 2.8 from the centre, three quarters of
    each lane, keeps its initial value: only the central disk is
    advanced."""
    import jax.numpy as jnp
    import numpy as np

    r2 = 0.0
    for ax, (n, ln) in enumerate(zip(config["global_shape"],
                                     config["lengths"])):
        c = (np.arange(n) + 0.5) * (ln / n) - ln / 2
        r2 = r2 + np.expand_dims(
            c * c, [a for a in range(len(config["global_shape"])) if a != ax])
    outside = r2 > 2.8 ** 2
    ad = _adapter()
    real = ad.run

    def run(p, leaves, steps, n):
        start = jnp.copy(leaves[0])  # the advance donates its input
        (out,) = real(p, leaves, steps, n)
        return (jnp.where(outside, start, out),)

    patch(ad, "run", run)
    return prog


def edges_free(prog, config, traffic, patch):
    """The held edges let go: the lane step updates the edge cells of the
    field as it does every other cell."""
    import jax.numpy as jnp

    from rocm_mpi_tpu.models import diffusion

    patch(diffusion, "global_boundary_mask",
          lambda grid, dtype=bool: jnp.zeros(grid.local_shape, dtype))
    return prog


FAULTS = {f.__name__: f for f in (unchanged, overrun, swapped, ring_frozen,
                                  edges_free)}

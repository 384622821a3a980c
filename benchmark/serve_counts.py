"""Bytes of the serving cells' lane step, from the configuration's
shapes alone (the reference's own accounting, not the program's)."""

from __future__ import annotations

import math


def lane_step_bytes(config: dict, itemsize: int = 4) -> int:
    """The reference's A_eff of one lane's step (diffusion_2D_perf.jl:56):
    the lane's T read and written and Cp read, each once: 3 * itemsize *
    the points of the request's field."""
    return 3 * itemsize * math.prod(config["global_shape"])

"""Bytes of the shallow-water cells' step, from the configuration's
shapes alone (the scheme's own accounting, not the program's)."""

from __future__ import annotations

from benchmark.counts import points_per_chip


def swe_bytes_per_step(config: dict, itemsize: int = 4) -> int:
    """The least HBM traffic of one forward-backward step per chip: each
    of the ndim + 1 state fields (h and one velocity per axis) read and
    written once, 2 * (ndim + 1) * itemsize * points. The face masks,
    fixed for the run, are left out, as SWERunResult.n_passes leaves
    them out."""
    ndim = len(config["global_shape"])
    return 2 * (ndim + 1) * itemsize * points_per_chip(config)

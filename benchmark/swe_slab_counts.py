"""Bytes of the boundary slabs of the sharded shallow-water hide step,
from the configuration's shape, mesh and b_width alone (the scheme's
own accounting, not the program's)."""

from __future__ import annotations


def slab_shapes(config: dict) -> list[tuple[int, int]]:
    """The (rows, columns) of each boundary slab of one chip's shard: the
    first and last b0 rows, then the first and last b1 columns of the
    rows between, with each b clamped to half the shard and to at least
    1 (parallel/overlap.effective_b_width)."""
    n0, n1 = (n // d for n, d in zip(config["global_shape"],
                                     config["dims"]))
    b0, b1 = (max(1, min(int(b), n // 2))
              for b, n in zip(config["b_width"], (n0, n1)))
    slabs = [(b0, n1)] * 2
    if n0 - 2 * b0 > 0:
        slabs += [(n0 - 2 * b0, b1)] * 2
    return slabs


def slab_bytes_per_step(config: dict, itemsize: int = 4) -> int:
    """The least HBM traffic of one step's slabs per chip: each of h, u
    and v read on the slab's cells and its one-cell ghost ring, and
    written on the slab's cells, once. The wall code, fixed for the run,
    is left out, as swe_counts leaves out the masks."""
    return 3 * itemsize * sum((m + 2) * (k + 2) + m * k
                              for m, k in slab_shapes(config))

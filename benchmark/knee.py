"""The knee of a serving cell: the highest arrival rate at which its
service keeps its queue bounded, found in one process. Not part of a
benchmark run; a cell's traffic file states the rate it chose.

    python3 benchmark/knee.py --workload <name> --seeds <n>,<m> \\
        --seconds 10 --rates 24,28,32

The service batches what its queue holds, so its batches widen as its
queue grows, and the rate it serves grows with them up to the rate of
full batches. That rate, its drain rate, is the knee: below it the
queue grows only until the batches are wide enough, above it the queue
grows without end. For each seed the cell's driver first runs its window
on a backlog of DRAIN requests with no arrivals, so that every batch is
full: the requests served a second are the drain rate. Then, as a test
of it, for each rate the window with the traffic's `rate_per_s` replaced
and no backlog. One JSON line each: requests offered and served a
second, the requests outstanding at each second, and the trend of that
count over the window's second half (least squares, requests a second).
The last line gives the knee, the median drain rate rounded down to a
whole request a second, and each rate's trends.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DRAIN = 512  # queued requests, more than a window of seconds serves


def trend(counts: list[int]) -> float:
    """Least-squares slope, a second, of the counts sampled each second,
    over their second half."""
    ys = counts[len(counts) // 2:]
    if len(ys) < 2:
        raise ValueError("too short a window for a trend")
    xs = range(len(ys))
    mx, my = (len(ys) - 1) / 2, sum(ys) / len(ys)
    return (sum((x - mx) * (y - my) for x, y in zip(xs, ys))
            / sum((x - mx) ** 2 for x in xs))


def main(argv=None, *, root: str = ROOT, require_chip: bool = True) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", required=True)
    args = p.parse_args(argv)
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import run, spec

    cell = spec.load_cell(root, args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".jax_cache")
    devices = run.chips_or_exit(cell.chips, require_chip)

    from rocm_mpi_tpu.utils.backend import enable_persistent_cache

    enable_persistent_cache()
    prog = cell.system.build(cell.config, cell.traffic, devices)
    seeds = [int(s) for s in args.seeds.split(",")]

    def window(seed, **traffic):
        at = types.SimpleNamespace(config=cell.config,
                                   traffic=dict(cell.traffic, **traffic))
        win = cell.driver.start(at, seed, prog, cell.reference).window(
            args.seconds)
        row = {**traffic, "seed": seed,
               "offered_per_s": win.submitted / args.seconds,
               "served_per_s": win.requests / win.seconds,
               "outstanding": win.outstanding,
               "trend_per_s": trend(win.outstanding)}
        print(json.dumps(row), flush=True)
        return row

    drains = []
    for s in seeds:
        row = window(s, rate_per_s=1e-9, backlog=DRAIN)
        if row["outstanding"][-1] == 0:
            raise SystemExit(f"the window served all {DRAIN} queued "
                             "requests: no drain rate")
        drains.append(row["served_per_s"])
    trends = {r: [window(s, rate_per_s=r, backlog=0)["trend_per_s"]
                  for s in seeds]
              for r in (float(x) for x in args.rates.split(","))}
    print(json.dumps({"workload": args.workload, "drain_per_s": drains,
                      "knee_per_s": math.floor(statistics.median(drains)),
                      "trends_per_s": trends}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""2D heat diffusion — communication/computation overlap variant (C4 analog).

The top rung of the ladder
(/root/reference/scripts/diffusion_2D_perf_hide.jl): boundary-frame strips
computed first, halo exchange overlapped with interior compute. This app
implements the reference's *intended* variant (3) semantics — full-frame
coverage plus halo exchange between boundary and interior completion — which
the reference shipped commented-out as "not ready yet" (hide.jl:94-101).
There are no user-managed queues/priorities/signals: the `ppermute` and the
interior update are dataflow-independent inside one shard_map program, so
XLA's latency-hiding scheduler overlaps them (the HSA-priority-queue analog,
SURVEY.md §2.2 D8). Reference defaults: fact=12 → 12288², nt=100,
b_width=(32,4).

The profiling twin (C5, …_perf_hide_prof.jl) is the --profile flag, not a
file fork: `--profile DIR` wraps the run in jax.profiler.trace (the
fields' set-up, the warmup and the timed steps), viewable in
TensorBoard/Perfetto.

  python apps/diffusion_2d_perf_hide.py --cpu-devices 8 --fact 0 --nx 512 --ny 512
  python apps/diffusion_2d_perf_hide.py --profile /tmp/trace
"""

import sys

from _common import make_parser, run_app

if __name__ == "__main__":
    parser = make_parser("hide", nx=12288, ny=12288, nt=100, do_vis=False)
    parser.set_defaults(dtype="f32")
    parser.add_argument(
        "--b-width",
        default="32,4",
        help="boundary frame width bx,by (hide.jl:42; clamped to shard/2)",
    )
    args = parser.parse_args()
    sys.exit(run_app("hide", args))

"""The shallow-water step's share of its HBM roofline: the least time the
chip could take for the window's steps, the state read and written once
a step (swe_counts.swe_bytes_per_step: 6 passes of 4-byte points in 2D)
over the peak HBM rate (peaks.json), divided by the device time of every
non-collective leaf op in the traced window (kernels, copies and fusions
alike, the harness's state copies included), averaged over the chips.
%, from the device trace."""

from benchmark import swe_counts, trace


def read(run):
    tr = run.trace
    if tr is None:
        return None
    chips = tr.chips()
    compute_s = sum(trace.measure(tr.compute(c)) for c in chips) / (
        1e9 * len(chips))
    if compute_s <= 0.0:
        return None
    least_s = (swe_counts.swe_bytes_per_step(run.config) * run.window.steps
               / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / compute_s

"""The shallow-water kernels' share of their HBM roofline: the least
time the chip could take for the window's steps
(swe_counts.swe_bytes_per_step) over the peak HBM rate (peaks.json),
divided by the device time of the Pallas kernels alone (custom-call
target tpu_custom_call; benchmark/layers.py), averaged over the chips.
swe_step_roofline divides by every compute op. The kernel also reads the
two face masks, 8 passes a step against the 6 counted, so it reads 75%
at most. %, from the device trace; nothing to read without kernels."""

from benchmark import layers, swe_counts


def read(run):
    tr = run.trace
    if tr is None:
        return None
    kernel_s = layers.seconds(tr, layers.is_kernel)
    if kernel_s <= 0.0:
        return None
    least_s = (swe_counts.swe_bytes_per_step(run.config) * run.window.steps
               / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / kernel_s

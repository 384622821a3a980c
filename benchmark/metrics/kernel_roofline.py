"""The kernels' share of their HBM roofline: the least time the chip
could take for the window's steps, the reference's A_eff
(counts.stencil_bytes_per_step) over the peak HBM rate (peaks.json),
divided by the device time of the Pallas kernels alone (custom-call
target tpu_custom_call; benchmark/layers.py), averaged over the chips.
stencil_roofline divides by every compute op; this one leaves out the
copies, slices and fusions around the kernels. %, from the device
trace; nothing to read without kernels."""

from benchmark import counts, layers


def read(run):
    tr = run.trace
    if tr is None:
        return None
    kernel_s = layers.seconds(tr, layers.is_kernel)
    if kernel_s <= 0.0:
        return None
    least_s = (counts.stencil_bytes_per_step(run.config) * run.window.steps
               / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / kernel_s

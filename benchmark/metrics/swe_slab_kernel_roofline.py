"""The sharded shallow-water hide step's slab kernels' share of their
HBM roofline: the least time a chip could take for a step's slabs
(swe_slab_counts.slab_bytes_per_step: h, u and v read with their
one-cell ghost ring and written once, on the slabs' cells) over the
peak HBM rate (peaks.json), divided by the device time a step of the
kernels tagged region="slab" (benchmark/layers.py), averaged over the
chips. %, from the device trace; nothing to read where no kernel
carries the tag."""

from benchmark import layers, swe_slab_counts


def read(run):
    slab_ms = layers.region_ms_per_step(run, "slab")
    if not slab_ms:
        return None
    least_ms = (1e3 * swe_slab_counts.slab_bytes_per_step(run.config)
                / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least_ms / slab_ms

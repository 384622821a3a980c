"""Device time a step of the overlap step's interior kernel: the
kernels tagged region="interior" (benchmark/layers.py), averaged over
the chips. ms, from the device trace; nothing to read where no kernel
carries the tag."""

from benchmark import layers


def read(run):
    return layers.region_ms_per_step(run, "interior")

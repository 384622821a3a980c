"""Device time a step of the sharded shallow-water hide step's interior,
the whole-shard `masked_swe_step`: interior_kernel_ms_per_step's
quantity (the kernels tagged region="interior", averaged over the
chips), read in the cell on the mesh. ms, from the device trace;
nothing to read where no kernel carries the tag."""

from benchmark.metrics.interior_kernel_ms_per_step import read  # noqa: F401

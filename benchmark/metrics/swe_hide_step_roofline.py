"""The sharded shallow-water hide step's share of its HBM roofline:
swe_step_roofline's quantity, read in the cell on the mesh. The least
time a chip could take for the window's steps, the state read and
written once a step (swe_counts.swe_bytes_per_step: 6 passes of 4-byte
points a chip), over the peak HBM rate, divided by the device time of
every non-collective leaf op in the traced window (the kernels, the
exchange's and the splice's copies, and the harness's state copies
alike), averaged over the chips. %, from the device trace."""

from benchmark.metrics.swe_step_roofline import read  # noqa: F401

"""Parallelism layer: device-mesh global grid, halo exchange, gather, overlap."""

from rocm_mpi_tpu.parallel.mesh import (  # noqa: F401
    BatchedGrid,
    GlobalGrid,
    init_batched_grid,
    init_global_grid,
    suggest_dims,
)
from rocm_mpi_tpu.parallel.gather import gather_to_host0  # noqa: F401
from rocm_mpi_tpu.parallel.halo import (  # noqa: F401
    HostStagedStepper,
    exchange_halo,
    exchange_into,
    exchange_slabs,
    ghosted_window,
    global_boundary_mask,
    neighbor_shift,
    place_core,
)
from rocm_mpi_tpu.parallel.ring import ring_exchange, ring_exchange_demo  # noqa: F401

"""Chip tier: compiled-Mosaic correctness on a real TPU.

The main `tests/` suite runs on a virtual 8-device CPU mesh with
interpret-mode Pallas — it can't see Mosaic (TPU compiler) bugs. This
tier runs every kernel path on the chip at tiny sizes and asserts
against the jnp oracle — the hardware analog of the reference's "run it
and check the output" acceptance step (/root/reference/README.md:14-19).

    chiprun -- python -m pytest tests_tpu/ -q

Off the TPU the tier refuses to run rather than skip: a green run here
must mean the kernels ran compiled on the chip. Unlike tests/conftest.py
this file forces no platform and no x64.
"""

import jax
import pytest

from rocm_mpi_tpu.utils.backend import enable_persistent_cache


def pytest_collection_modifyitems(config, items):
    if items and jax.devices()[0].platform != "tpu":
        raise pytest.UsageError(
            "tests_tpu/ needs a TPU (jax.devices()[0].platform="
            f"{jax.devices()[0].platform!r}); run it through the chip tool"
        )
    enable_persistent_cache()

"""Compiled-mode (real TPU) kernel tests.

Unlike `tests/` (which pins JAX to an 8-virtual-device CPU mesh so sharding
semantics run anywhere), this suite runs the Pallas kernels through the real
Mosaic compiler on an actual TPU chip. Round 2 shipped a kernel that passed
every interpret-mode test and died on silicon with a tiling error — this
suite exists so that class of bug fails in CI, not in the benchmark.

Run: `python -m pytest tests_tpu/ -q` on a machine with a TPU. Without one —
or with one that fails to initialise — the run is an error, never a green
column of skips.
"""
import jax
import pytest

from paddle_tpu.utils.compile_cache import enable_compile_cache


def pytest_sessionstart(session):
    platform = jax.devices()[0].platform     # raises if the TPU fails to init
    if platform != "tpu":
        raise pytest.UsageError(
            f"tests_tpu/ needs a TPU backend; jax found {platform!r}")
    enable_compile_cache()

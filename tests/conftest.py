"""Test harness: simulate an 8-device TPU-like mesh on CPU.

The reference has no tests (SURVEY.md §4) — correctness there requires ≥4
real GPUs + MPI. Here every distributed schedule runs single-process on 8
virtual CPU devices, so halo/pipeline/GEMS can be validated bit-for-bit
against single-device golden models in CI.

The suite is a CPU-mesh suite wherever it runs: the platform is pinned
here, before first backend use, so it needs no ``JAX_PLATFORMS`` from
the caller.
"""

import os

import jax

jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache for the suite itself: the fast tier's wall
# time is dominated by CPU XLA compiles of the golden train steps, which
# are identical from run to run. Keyed by program+platform, so correctness
# is jax's concern, not ours; a cold run warms it (~7 min), warm reruns of
# the fast tier fit the <5-minute CI window (measured — README "Testing").
from mpi4dl_tpu.utils import enable_compilation_cache

enable_compilation_cache(
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 ".cache", "jax-cpu-tests")
)
# The suite keeps JAX's own key, without the name stacks the program's runs
# put into theirs (benchmarks.common.build_config, PR 41): many test files
# compile the same tiny step from other lines, and with the lines in the key
# none would share an entry (a cold run took 972 s where 560). Empty
# .cache/jax-cpu-tests after a change to the program's ``jax.named_scope``s:
# an entry from before it carries the older names. Before every test, since
# a test that walks ``benchmarks.common.build_config`` turns it on again.
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _suite_cache_key_without_name_stacks():
    jax.config.update("jax_compilation_cache_include_metadata_in_key", False)

# Golden-parity tests compare distributed (tile-local shapes) against
# single-device (full-image) runs; the MXU-packed conv picks pack factors
# from local shapes, so the two sides could legally differ in f32
# accumulation order. Pin the suite to the stock conv impl so parity
# assertions are platform-independent; tests/test_fastconv.py opts back in
# per-test to validate the packed path itself.
os.environ["MPI4DL_TPU_CONV_IMPL"] = "xla"

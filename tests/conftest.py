"""Test harness configuration.

The reference exercised "multi-node" logic as multi-process NCCL on one host
(``tests/unit/common.py:16-105``).  Here the analogous trick is a *virtual
multi-chip mesh*: ``--xla_force_host_platform_device_count=8`` gives 8 CPU
devices in one process, and meshes/shardings built over them execute the
same SPMD programs (same collectives, same partitioning) that run on a real
pod.  These env vars must be set before jax initializes its backends, hence
the module-level code in conftest.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import pytest  # noqa: E402

# Tests run on the CPU with 8 virtual devices.  DS_TEST_TPU=1 leaves the
# platform alone so the ``-m tpu`` compiled-kernel suite reaches the chip
# (``DS_TEST_TPU=1 pytest -m tpu``).
_WANT_TPU = os.environ.get("DS_TEST_TPU") == "1"
if not _WANT_TPU:
    jax.config.update("jax_platforms", "cpu")
    os.environ["JAX_PLATFORMS"] = "cpu"  # for any subprocesses tests spawn

# Persistent compile cache, by the package's one rule
# (runtime/compilation/cache.py): JAX_COMPILATION_CACHE_DIR where it is
# set, else <checkout>/.jax_cache.  Only programs that took 0.5 s or more
# to compile are kept: the suite compiles thousands of tiny ones.
from deepspeed_tpu.runtime.compilation.cache import default_cache_dir  # noqa: E402

if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir", default_cache_dir())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


def pytest_collection_modifyitems(config, items):
    if _WANT_TPU:
        jax.devices("tpu")  # raises without a chip: asked for, never skipped
        return
    skip_tpu = pytest.mark.skip(
        reason="needs a TPU (run: DS_TEST_TPU=1 pytest -m tpu)")
    for item in items:
        if "tpu" in item.keywords:
            item.add_marker(skip_tpu)


@pytest.fixture(scope="session")
def cpu_devices():
    devs = jax.devices("cpu")
    assert len(devs) >= 8, f"expected >=8 virtual cpu devices, got {len(devs)}"
    return devs


@pytest.fixture(autouse=True)
def _default_cpu(request):
    """Run unsharded computations on CPU regardless of the default backend —
    EXCEPT for ``-m tpu`` tests, which exist precisely to exercise compiled
    kernels on the real chip (pinning them to CPU made pallas_call fail
    with 'Only interpret mode is supported on CPU backend')."""
    if request.node.get_closest_marker("tpu"):
        with jax.default_device(jax.devices("tpu")[0]):
            yield
        return
    cpu0 = jax.devices("cpu")[0]
    with jax.default_device(cpu0):
        yield

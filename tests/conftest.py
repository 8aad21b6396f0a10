"""Test configuration: run the whole suite on an 8-device virtual CPU mesh.

This is the TPU analogue of the reference's "gloo on localhost" multi-process
test trick (reference: test/parallel/ run under horovodrun with 2 local ranks,
SURVEY §4): `xla_force_host_platform_device_count=8` gives 8 XLA CPU devices in
one process, so every collective, sharding, and mesh-decomposition path is
exercised exactly as it would compile for an 8-chip slice.
"""

import os

# Must run before jax initializes its backends. Tests never touch the
# accelerator: they run with JAX_PLATFORMS=cpu on 8 virtual host devices.
# The chip is reached only through `python chip_smoke.py` (and `python
# bench.py`), one process at a time — see the README "Development" block.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import pytest  # noqa: E402

import horovod_tpu as hvd  # noqa: E402


@pytest.fixture()
def hvd_ctx():
    """Initialized 1D 8-chip context, torn down after the test."""
    ctx = hvd.init()
    yield ctx
    hvd.shutdown()


@pytest.fixture()
def hvd_ctx_2d():
    """Hierarchical (cross=2, local=4) mesh context."""
    ctx = hvd.init(mesh_shape=(2, 4))
    yield ctx
    hvd.shutdown()


@pytest.fixture(autouse=True)
def _clean_state():
    yield
    # Tracing reset BEFORE shutdown: a test that left the recorder on
    # must not make the teardown's hvd.shutdown() export a merged trace
    # into the repo CWD.
    from horovod_tpu.tracing import spans as _spans
    from horovod_tpu.tracing import straggler as _straggler
    _spans.reset()
    _straggler.install(None)
    if hvd.is_initialized():
        hvd.shutdown()
    from horovod_tpu.stall_inspector import get_stall_inspector
    get_stall_inspector().reset()
    # Knob overrides are process-wide: an autotune ParameterManager that
    # applied its dims leaves HOROVOD_TORUS_ALLREDUCE & co. overridden,
    # and the next test FILE on the same xdist worker then reads them
    # (test_process_sets' torus test failed after test_dcn_tier or
    # test_faults, depending on how the files fell to the workers).
    from horovod_tpu.config import knobs
    knobs.clear_all_overrides()

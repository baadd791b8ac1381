"""Test configuration: 8 virtual CPU devices simulating a TPU slice.

SURVEY.md §4: the reference tests collectives by launching ≥2 real processes
over Gloo/MPI shared memory.  JAX lets us do strictly better — a virtual
8-device mesh in one process (``--xla_force_host_platform_device_count``)
exercises the same XLA collective code paths that run over ICI on hardware.

The suite never touches an accelerator: the platform is forced to the CPU
below whatever the environment says, and child processes get
``JAX_PLATFORMS=cpu`` in their environment.
"""

import os

os.environ.setdefault("HOROVOD_CYCLE_TIME", "0.1")  # fast test cycles (ms)
# hvd.init() points jax's persistent compilation cache at
# <checkout>/.jax_cache; tests (and the children that inherit this
# environment) compile every time so that a run never depends on what an
# earlier run left on disk
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def hvd():
    import horovod_tpu as hvd
    hvd.init()
    yield hvd
    hvd.shutdown()


@pytest.fixture(scope="session")
def n_workers(hvd):
    return hvd.size()


@pytest.fixture(scope="session")
def sp_mesh(hvd):
    """8-way sequence-parallel mesh shared by the parallel test modules."""
    return jax.make_mesh((8,), ("sp",))


@pytest.fixture(scope="session")
def tfhvd(hvd):
    """TF adapter over the initialized engine (importorskip at use sites)."""
    import horovod_tpu.tensorflow as tfhvd
    return tfhvd


@pytest.fixture(scope="session")
def thvd(hvd):
    """Torch adapter over the initialized engine."""
    import horovod_tpu.torch as thvd
    return thvd


@pytest.fixture
def pallas_interpret(monkeypatch):
    """The Pallas kernels of every file under ``ops/`` run here, on the
    CPU, interpreted: the one flag they all read, on for the test that
    asks for the fixture.  What it gives is the switch, for a case that
    compares both paths: ``pallas_interpret(path == "pallas")``."""
    from horovod_tpu.ops import _pallas

    def switch(on=True):
        monkeypatch.setattr(_pallas, "INTERPRET", bool(on))

    switch()
    return switch

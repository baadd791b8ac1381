"""TpuExecutor (L5 worker-pool) tests.

Reference parity: ``test/single/test_ray.py`` — start an executor pool,
run functions on all workers repeatedly, assert per-rank results and
persistent state between calls, clean shutdown and failure surfaces.
"""

import os

import pytest

from _helpers import free_port

from horovod_tpu.runner import TpuExecutor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env():
    return {
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO + ":" + os.path.join(REPO, "tests"),
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "HOROVOD_CYCLE_TIME": "0.2",
    }


def _topology():
    import horovod_tpu as hvd
    return {"rank": hvd.cross_rank(), "size": hvd.size()}


def _bump_counter():
    import horovod_tpu as hvd  # noqa: F401 - runtime stays initialized
    import builtins
    builtins._hvd_exec_counter = getattr(
        builtins, "_hvd_exec_counter", 0) + 1
    return builtins._hvd_exec_counter


def _allreduce_rank():
    import numpy as np
    import horovod_tpu as hvd
    out = hvd.allreduce(np.float32(hvd.cross_rank() + 1.0), op=hvd.Sum,
                        name="exec_ar")
    return float(np.asarray(out))


def _boom():
    raise ValueError("deliberate task failure")


def test_executor_pool_persistent_state():
    with TpuExecutor(np=2, env=_env(), port=free_port()) as ex:
        topo = ex.run(_topology)
        assert [t["rank"] for t in topo] == [0, 1]
        assert all(t["size"] == 2 for t in topo)
        # workers persist between calls: the counter accumulates
        assert ex.run(_bump_counter) == [1, 1]
        assert ex.run(_bump_counter) == [2, 2]
        # a REAL cross-process collective through the warm pool
        assert ex.run(_allreduce_rank) == [3.0, 3.0]


def test_executor_task_failure_surfaces():
    with TpuExecutor(np=2, env=_env(), port=free_port()) as ex:
        with pytest.raises(RuntimeError, match="deliberate task failure"):
            ex.run(_boom)


def test_executor_run_remote_fetch():
    with TpuExecutor(np=2, env=_env(), port=free_port()) as ex:
        t1 = ex.run_remote(_bump_counter)
        t2 = ex.run_remote(_bump_counter)
        assert ex.fetch(t1) == [1, 1]
        assert ex.fetch(t2) == [2, 2]


def test_executor_requires_start():
    ex = TpuExecutor(np=1)
    with pytest.raises(RuntimeError, match="not started"):
        ex.run(_topology)


def _exit_nonzero():
    raise SystemExit(3)


def test_executor_startup_failure_cleans_up(tmp_path):
    """A worker dying during startup must stop survivors and reclaim the
    control dir (review regression)."""
    bad_env = _env()
    bad_env["XLA_FLAGS"] = "--definitely-not-a-flag"
    ex = TpuExecutor(np=2, env=bad_env, port=free_port())
    with pytest.raises(RuntimeError):
        ex.start(timeout_s=30)
    assert ex._procs is None and ex._tmp is None

"""TpuExecutor worker-pool demo (L5 tier — reference: examples/ray/).

Starts a persistent 2-worker pool, runs several functions on it without
re-paying rendezvous or compile setup between calls, and shuts down.

Run: python examples/executor_pool.py
"""

from horovod_tpu.runner import TpuExecutor


def topology():
    import horovod_tpu as hvd
    return f"rank {hvd.cross_rank()}/{hvd.cross_size()}, " \
           f"{hvd.size()} workers"


def train_step(scale):
    import numpy as np
    import horovod_tpu as hvd
    grad = np.ones(4, np.float32) * (hvd.cross_rank() + 1) * scale
    return hvd.allreduce(grad, name="grad").tolist()


def main():
    env = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "HOROVOD_CYCLE_TIME": "0.2",
    }
    with TpuExecutor(np=2, env=env) as ex:
        print("pool:", ex.run(topology))
        # repeated calls reuse the warm runtime + compiled kernels
        for step, scale in enumerate([1.0, 2.0, 3.0]):
            outs = ex.run(train_step, args=(scale,))
            print(f"step {step}: averaged grads {outs[0][:2]}...")


if __name__ == "__main__":
    main()

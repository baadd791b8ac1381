"""FSDP (ZeRO-3 class) Llama training on a device mesh.

Params, grads and optimizer state all live dp-sharded; each layer's
weights are all-gathered just-in-time inside the compiled step.  With 8
devices the per-chip model+optimizer memory is 1/8 of a replicated-DP
run — the knob that turns "fits on a slice" into "fits on a chip".

Run on the 8-device virtual CPU mesh:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/fsdp_llama.py

(or on a real slice, where the all-gathers ride ICI).
"""

import os
import sys

if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
import optax

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from horovod_tpu import training                           # noqa: E402
from horovod_tpu.models import llama                       # noqa: E402
from horovod_tpu.optim.precision import adamw_lp           # noqa: E402
from horovod_tpu.parallel.mesh import MeshConfig, ParallelMesh  # noqa: E402


def main():
    n = jax.local_device_count()
    cfg = llama.LlamaConfig(
        vocab_size=2048, d_model=256, n_layers=8, n_heads=8, n_kv_heads=4,
        d_ff=1024, max_seq_len=256,
        dtype=jnp.float32 if jax.devices()[0].platform == "cpu"
        else jnp.bfloat16)
    pmesh = ParallelMesh(MeshConfig(dp=n))
    # bf16-moment AdamW: with FSDP the optimizer state is ALSO sharded,
    # so total optimizer HBM is 4 bytes/param ÷ n devices
    ts = training.make_llama_fsdp_step(cfg, pmesh, optimizer=adamw_lp(3e-4))
    params, opt_state = ts.init_fn(jax.random.PRNGKey(0))

    wq = params["layers"]["wq"]
    print(f"devices={n}  params={llama.count_params(cfg)/1e6:.1f}M  "
          f"wq per-device shard: {wq.addressable_shards[0].data.shape} "
          f"of {wq.shape}")

    rng = np.random.RandomState(0)
    sh = training.make_data_sharding(ts)
    for step in range(10):
        toks = jax.device_put(jnp.asarray(
            rng.randint(0, cfg.vocab_size, (4 * n, 256)), jnp.int32), sh)
        params, opt_state, loss = ts.step_fn(params, opt_state, toks, toks)
        if step % 3 == 0:
            print(f"step {step}: loss={float(loss):.4f}")
    print("done")


if __name__ == "__main__":
    main()

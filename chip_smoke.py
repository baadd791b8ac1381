"""Chip smoke: the trainer's main path, once, on every chip jax finds.

    python chip_smoke.py

One process, no children.  Runs the 1B decoder preset of ``bench.py`` at
its full width through the entry points a user calls — ``hvd.init()``,
the eager collectives through the engine, ``training.make_llama_train_step``
over ``ParallelMesh(MeshConfig(dp=N))`` — and checks what comes out.
Fails (non-zero exit, no result line) unless ``jax.devices()[0]`` is a
TPU.  The last line of standard output is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
the line before it, ``summary: {...}``, is one JSON object that ends
``"claim": null``: the times it carries are set-up information for sizing
later runs, not metrics.

The phases are functions of a :class:`SmokeConfig`, so
``tests/test_chip_smoke.py`` drives them at ``llama.tiny()`` size on the
CPU mesh; only :func:`main` insists on a TPU.
"""

import dataclasses
import importlib.metadata
import json
import logging
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import jaxlib
import numpy as np

import horovod_tpu as hvd
from horovod_tpu import training
from horovod_tpu.models import llama
from horovod_tpu.native import loader as native_loader
from horovod_tpu.ops import _pallas, flash_attention
from horovod_tpu.optim.precision import adamw_lp
from horovod_tpu.parallel.mesh import MeshConfig, ParallelMesh
from horovod_tpu.runtime import use_compile_cache


class SmokeFailure(Exception):
    """A phase's check did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


@dataclasses.dataclass(frozen=True)
class SmokeConfig:
    model: llama.LlamaConfig
    per_chip_batch: int
    seq: int
    steps: int


def full_width() -> SmokeConfig:
    """The 1B preset exactly as ``bench.py`` ``main()`` builds it, except
    that ``loss_chunk`` divides the sequence (bench's 2048 does not divide
    1024, so the head it names is not the head that runs there)."""
    return SmokeConfig(
        model=llama.LlamaConfig(
            vocab_size=32768, d_model=2048, n_layers=16, n_heads=16,
            n_kv_heads=8, d_ff=8192, max_seq_len=1024, remat=True,
            remat_policy="full", remat_skip_layers=2, loss_chunk=1024),
        per_chip_batch=8, seq=1024, steps=6)


def device_phase() -> dict:
    """What jax found, and with which installation."""
    dev = jax.devices()[0]
    facts = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": jax.device_count(),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": importlib.metadata.version("libtpu"),
        "compile_cache_dir": use_compile_cache(),
    }
    print(f"device: {facts}", flush=True)
    return facts


def runtime_phase() -> str:
    """``hvd.init()`` maps one worker onto each chip of this process.
    Returns which control plane the engine got: native or python."""
    n = jax.device_count()
    hvd.init()
    check(hvd.size() == n, f"hvd.size() {hvd.size()} != {n} devices")
    check(hvd.local_size() == n, f"hvd.local_size() {hvd.local_size()} != {n}")
    check(hvd.rank() == 0, f"hvd.rank() {hvd.rank()} != 0")
    mesh_devices = list(hvd.mesh().devices.flat)
    check(len(set(mesh_devices)) == n,
          f"hvd.mesh() holds {len(set(mesh_devices))} distinct devices, "
          f"not {n}")
    check({d.platform for d in mesh_devices} == {jax.devices()[0].platform},
          "hvd.mesh() mixes platforms")
    plane = "native" if native_loader.load() is not None else "python"
    print(f"runtime: size={n} control_plane={plane}", flush=True)
    return plane


def _check_one_shard_per_device(shards, what: str) -> None:
    found = len({s.device for s in shards})
    check(found == jax.device_count(),
          f"{what} sits on {found} devices, not {jax.device_count()}")


def _check_on_every_worker(out, expect: np.ndarray, what: str) -> None:
    """``out`` is replicated: one shard per device, each equal to
    ``expect``."""
    shards = out.addressable_shards
    _check_one_shard_per_device(shards, what)
    for s in shards:
        got = np.asarray(s.data.astype(jnp.float32))
        check(np.array_equal(got, expect),
              f"{what}: worker on {s.device} holds {got.ravel()[:4]}, "
              f"expected {expect.ravel()[:4]}")


def eager_phase() -> None:
    """Rank-dependent values through the background engine."""
    n = hvd.size()
    total = np.full((256,), n * (n + 1) / 2, np.float32)
    for dtype in (np.float32, jnp.bfloat16):
        out = hvd.allreduce(
            hvd.worker_values(lambda r: np.full((256,), r + 1, dtype)),
            op=hvd.Sum, name=f"smoke.sum.{np.dtype(dtype).name}")
        check(out.dtype == dtype, f"allreduce returned {out.dtype}")
        _check_on_every_worker(out, total, f"allreduce {out.dtype}")
    out = hvd.broadcast(
        hvd.worker_values(lambda r: np.full((8,), r, np.float32)), n - 1,
        name="smoke.bcast")
    _check_on_every_worker(out, np.full((8,), n - 1, np.float32),
                           "broadcast")
    out = hvd.allgather(
        hvd.worker_values(lambda r: np.full((2, 4), r, np.float32)),
        name="smoke.gather")
    _check_on_every_worker(
        out, np.repeat(np.arange(n, dtype=np.float32), 2)[:, None]
        * np.ones((1, 4), np.float32), "allgather")
    handle = hvd.allreduce_async(
        hvd.worker_values(lambda r: np.full((256,), r + 1, np.float32)),
        op=hvd.Sum, name="smoke.async")
    _check_on_every_worker(hvd.synchronize(handle), total, "allreduce_async")
    print(f"eager: allreduce/broadcast/allgather/async ok on {n} workers",
          flush=True)


class _Messages(logging.Handler):
    def __init__(self):
        super().__init__(logging.WARNING)
        self.messages = []

    def emit(self, record):
        self.messages.append(record.getMessage())


def _check_replicas_identical(params) -> None:
    """Every device's copy of every parameter leaf has the same bits."""
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        name = jax.tree_util.keystr(path)
        shards = leaf.addressable_shards
        _check_one_shard_per_device(shards, name)
        first = np.asarray(shards[0].data)
        check(first.shape == leaf.shape, f"{name} is not replicated")
        for s in shards[1:]:
            check(np.array_equal(first.view(np.uint8),
                                 np.asarray(s.data).view(np.uint8)),
                  f"{name}: replica on {s.device} differs from "
                  f"{shards[0].device}")


def trainer_phase(cfg: SmokeConfig) -> dict:
    """``cfg.steps`` data-parallel steps of ``cfg.model`` on one fixed batch."""
    n = jax.device_count()
    m = cfg.model
    ts = training.make_llama_train_step(
        m, ParallelMesh(MeshConfig(dp=n)), optimizer=adamw_lp(3e-4))
    params, opt_state = ts.init_fn(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    sharding = training.make_data_sharding(ts)
    shape = (cfg.per_chip_batch * n, cfg.seq)
    toks, tgts = (
        jax.device_put(jnp.asarray(rng.randint(0, m.vocab_size, shape),
                                   jnp.int32), sharding) for _ in range(2))
    _check_one_shard_per_device(toks.addressable_shards, "the batch")
    rows = [np.asarray(s.data) for s in toks.addressable_shards]
    check(all(r.shape == (cfg.per_chip_batch, cfg.seq) for r in rows)
          and all(not np.array_equal(rows[0], r) for r in rows[1:]),
          "each chip must receive its own batch shard")

    q = jax.ShapeDtypeStruct(
        (cfg.per_chip_batch, cfg.seq, m.n_heads, m.head_dim), m.dtype)
    kv = jax.ShapeDtypeStruct(
        (cfg.per_chip_batch, cfg.seq, m.n_kv_heads, m.head_dim), m.dtype)
    facts = {
        "flash_supported": flash_attention.supported(q, kv, kv),
        "interpret": _pallas.INTERPRET,
    }

    logger = logging.getLogger("horovod_tpu")
    heard = _Messages()
    logger.addHandler(heard)
    try:
        facts["tpu_custom_call"] = "tpu_custom_call" in ts.step_fn.lower(
            params, opt_state, toks, tgts).as_text()
        t0 = time.perf_counter()
        params, opt_state, loss = jax.block_until_ready(
            ts.step_fn(params, opt_state, toks, tgts))
        facts["first_call_s"] = round(time.perf_counter() - t0, 3)
    finally:
        logger.removeHandler(heard)
    fell_back = [msg for msg in heard.messages if "falling back" in msg]
    check(not fell_back, f"the step fell back while tracing: {fell_back}")

    losses, step_s = [float(loss)], []
    for _ in range(cfg.steps - 1):
        t0 = time.perf_counter()
        params, opt_state, loss = jax.block_until_ready(
            ts.step_fn(params, opt_state, toks, tgts))
        step_s.append(time.perf_counter() - t0)
        losses.append(float(loss))
    check(all(np.isfinite(losses)), f"non-finite loss in {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    _check_replicas_identical(params)

    facts.update({
        "model": {"vocab": m.vocab_size, "d_model": m.d_model,
                  "layers": m.n_layers, "heads": m.n_heads,
                  "kv_heads": m.n_kv_heads, "d_ff": m.d_ff,
                  "loss_chunk": m.loss_chunk},
        "seq": cfg.seq, "per_chip_batch": cfg.per_chip_batch,
        "losses": [round(x, 4) for x in losses],
        "steady_step_s": round(statistics.median(step_s), 4),
        "peak_bytes_in_use": [
            (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()],
        "replicas_identical": True,
    })
    print(f"trainer: {facts}", flush=True)
    return facts


def result_line(device: dict) -> str:
    """The last line of standard output: these two keys and no others (the
    driver's check reads it)."""
    return json.dumps({
        "ok": True,
        "device": {k: device[k] for k in ("platform", "kind", "count")},
    })


def main() -> int:
    found = jax.devices()[0]
    if found.platform != "tpu":
        # before anything reaches standard output: no result without a chip
        sys.exit(f"chip_smoke: no TPU — jax found platform "
                 f"{found.platform!r} ({found.device_kind})")
    device = device_phase()
    plane = runtime_phase()
    eager_phase()
    cfg = full_width()
    trainer = trainer_phase(cfg)
    check(trainer["flash_supported"],
          "flash_attention.supported() refused the step's q/k/v shapes")
    check(trainer["tpu_custom_call"],
          "the lowered step holds no tpu_custom_call: no Mosaic kernel")
    check(not trainer["interpret"], "a Pallas kernel is in interpret mode")
    hvd.shutdown()
    last = result_line(device)
    print("summary: " + json.dumps({
        **json.loads(last),
        "versions": {k: device[k] for k in ("jax", "jaxlib", "libtpu")},
        "compile_cache_dir": device["compile_cache_dir"],
        "control_plane": plane,
        "trainer": trainer,
        "claim": None,
    }), flush=True)
    print(last, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

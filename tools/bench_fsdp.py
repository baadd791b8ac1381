#!/usr/bin/env python
"""Mesh-axis-aware gradient-plane microbench: the 2-D (data x model)
composition of ZeRO tiles, quantized wire, and overlap taps (ISSUE 14).

Measures what the spec-aware refactor changes on a virtual 2-D CPU mesh
(``shard_map`` over ``--xla_force_host_platform_device_count``
devices on a mesh of axes ``data`` and ``model``).  Params are
model-sharded (`PartitionSpec("model")` on the stacked layer weights,
replicated norms/embed); gradients w.r.t. the LOCAL shards arrive
pre-reduced over the model axis (the in-program gather's transpose),
and ``DistributedGradientTransform(param_specs=...)`` does the rest.
Four gates, all asserted every run:

  * **per-chip bytes at the model-shard fraction (exact)**:
    ``tree_nbytes`` of one chip's params == the leaf-wise sharded
    fraction, and the ZeRO config's inner optimizer state == the exact
    tile bytes of ``optim.distributed.sharded_tile_layout`` —
    ``total/(model x data)`` + padding, not an approximation.
  * **DCN (data-hop) wire bytes**: priced from traced schedules under
    ``analysis/wire.py`` STRICT accounting — the spec-aware schedule's
    data hop must carry the model-shard fraction of the replicated
    plan's bytes, and int8 on top must shrink it >= 3.5x further.
  * **one-program A/B bit-identical weights**: for each of
    plain / zero / int8 / int8+zero, ONE compiled program with a
    runtime ``fire`` gate (``overlapped_backprop(tx, fire=...)``) runs
    overlapped dispatch in the true branch and the identical boundary
    plan in the false branch — weights must be BIT-identical, on the
    2-D mesh, spec-aware plans included.
  * **spec-aware == replicated parity**: the same trajectory on a flat
    1-D mesh of data*model devices with full replicated params lands
    on the same weights (allclose: the reduction tree differs, so ulps
    may).

    python tools/bench_fsdp.py               # 2x2 mesh
    python tools/bench_fsdp.py --smoke       # CI: fast, asserts only

Results print as JSON; see docs/performance.md "Mesh-axis-aware
sharding".
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _setup_jax(n_devices: int):
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}")
    import jax
    jax.config.update("jax_platforms", "cpu")
    return jax


def _make_params(jax, n_layers: int, width: int):
    """Scanned-model tree: stacked layer weights (model-sharded on the
    per-layer row dim) + replicated root leaves; odd embed rows so
    bucket padding is exercised."""
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.default_rng(0)

    def r(*shape):
        return jnp.asarray(rng.standard_normal(shape) * 0.1, jnp.float32)

    return {
        "embed": r(width // 2 + 3, width),
        "layers": {
            "w": r(n_layers, width, width),
            "b": jnp.zeros((n_layers, width), jnp.float32),
        },
        "final_norm": jnp.ones((width,), jnp.float32),
    }


def _specs(jax):
    from jax.sharding import PartitionSpec as P
    return {
        "embed": P(),
        # stacked [L, W, W]: per-layer rows shard over model
        "layers": {"w": P(None, "model"), "b": P()},
        "final_norm": P(),
    }


def _carve(jax, params, M):
    """The (model-rank-local) param shards, inside the mapped program."""
    from jax import lax
    idx = lax.axis_index("model")
    W = params["layers"]["w"].shape[1]
    out = dict(params)
    out["layers"] = dict(params["layers"])
    out["layers"]["w"] = lax.dynamic_slice_in_dim(
        params["layers"]["w"], idx * (W // M), W // M, axis=1)
    return out


def _model_loss(jax, ov, params_local, x):
    """Toy scanned model computing with gathered-full layer weights:
    the gather's transpose is what delivers shard-shaped, model-reduced
    gradients to the taps/transform — the FSDP gradient contract."""
    import jax.numpy as jnp
    from jax import lax
    params_local = ov.tap_root(params_local)
    h = x @ params_local["embed"]

    def body(h, lp):
        lp = ov.grad_tap(lp)
        w_full = lax.all_gather(lp["w"], "model", axis=0, tiled=True)
        h = jnp.tanh(h @ w_full + lp["b"])
        return h, None

    h, _ = lax.scan(body, h, params_local["layers"])
    return ((h * params_local["final_norm"]) ** 2).sum()


def _tx(sharded, wire, specs, threshold, axis="data", model_axes=("model",),
        overlap=True, block=16):
    import optax
    from horovod_tpu.optim.distributed import DistributedOptimizer
    return DistributedOptimizer(
        optax.adam(1e-2), axis_name=axis, threshold_bytes=threshold,
        overlap=overlap, sharded_update=sharded,
        wire_format=wire or "none", wire_block_size=block if wire else None,
        param_specs=specs, model_axes=model_axes if specs else None)


def _run_ab(jax, tx, params, D, M, steps):
    """One compiled program, fire on/off: bit-exact weights on the 2-D
    mesh; returns the fire-on weights (replica 0,0)."""
    import functools
    import numpy as np
    import optax
    import horovod_tpu as hvd
    from horovod_tpu.optim import overlap as ov
    loss_fn = functools.partial(_model_loss, jax, ov)
    rng = np.random.default_rng(1)
    X = jax.numpy.asarray(
        rng.standard_normal((D, M, 2, params["embed"].shape[0])),
        jax.numpy.float32)

    def prog(x, fire):
        p = _carve(jax, params, M)
        s = tx.init(p)
        for _ in range(steps):
            with hvd.overlapped_backprop(tx, fire=fire):
                _l, g = jax.value_and_grad(loss_fn)(p, x)
            u, s = tx.update(g, s, p)
            p = optax.apply_updates(p, u)
        return p, s

    from jax.sharding import PartitionSpec as P
    piece = P("data", "model")

    def on_a_device(x, fire):       # a leaf [1, 1, ...] in, and out
        return jax.tree_util.tree_map(
            lambda r: jax.numpy.asarray(r)[None, None], prog(x[0, 0], fire))

    f = jax.jit(jax.shard_map(
        on_a_device, mesh=jax.make_mesh((D, M), ("data", "model")),
        in_specs=(piece, P()), out_specs=piece, check_vma=False))
    # to the host: a caller indexes replicas, which an array laid out on
    # the mesh's named axes does not let it do
    run = lambda fire: jax.tree_util.tree_map(
        np.asarray, f(X, jax.numpy.asarray(fire)))
    p_on, s_on = run(True)
    p_off, _ = run(False)
    for a, b in zip(jax.tree_util.tree_leaves(p_on),
                    jax.tree_util.tree_leaves(p_off)):
        a, b = np.asarray(a), np.asarray(b)
        assert (a == b).all(), \
            f"weights not bit-identical: max delta {np.abs(a - b).max()}"
    for leaf in jax.tree_util.tree_leaves(p_on):
        leaf = np.asarray(leaf)
        # data-replicas must agree (model shards legitimately differ)
        assert (leaf[0] == leaf[-1]).all(), "data replicas diverged"
    return p_on, s_on


def _local_shapes(jax, params, M):
    """ShapeDtypeStructs of one model-rank's param shards (M=1: the
    full replicated shapes)."""
    import jax.numpy as jnp
    sds = jax.ShapeDtypeStruct
    L, W = params["layers"]["b"].shape
    return {
        "embed": sds(params["embed"].shape, jnp.float32),
        "layers": {"w": sds((L, W // M, W), jnp.float32),
                   "b": sds((L, W), jnp.float32)},
        "final_norm": sds((W,), jnp.float32),
    }


def _trace_wire(jax, tx, params, D, M, sharded_operands: bool):
    """Per-worker DATA-hop (DCN analog) ring bytes of the traced step,
    strict accounting.  ``sharded_operands=False`` traces the
    replicated baseline: the same step over FULL-width buffers — the
    bytes the data hop paid before the gradient plane was mesh-aware."""
    from horovod_tpu.analysis.schedule import trace_schedule
    from horovod_tpu.analysis.wire import schedule_transmit_bytes
    local = _local_shapes(jax, params, M if sharded_operands else 1)

    def step(g, p):
        u, _ = tx.update(g, tx.init(p), p)
        return u

    sched = trace_schedule(step, (local, local),
                           axis_env=[("data", D), ("model", M)],
                           entry="bench_fsdp")
    return schedule_transmit_bytes(sched, axis_filter="data", strict=True)


def _replicated_reference(jax, params, n, threshold, steps):
    """The same trajectory on a flat 1-D replicated mesh of n devices."""
    import functools
    import numpy as np
    import optax
    import horovod_tpu as hvd
    from horovod_tpu.optim import overlap as ov
    tx = _tx(False, None, None, threshold, axis="flat", model_axes=None)
    loss_fn = functools.partial(_model_loss_flat, jax, ov)
    rng = np.random.default_rng(1)
    X = jax.numpy.asarray(
        rng.standard_normal((n, 2, params["embed"].shape[0])),
        jax.numpy.float32)

    def prog(x):
        p = params
        s = tx.init(p)
        for _ in range(steps):
            with hvd.overlapped_backprop(tx, fire=jax.numpy.asarray(
                    False)):
                _l, g = jax.value_and_grad(loss_fn)(p, x)
            u, s = tx.update(g, s, p)
            p = optax.apply_updates(p, u)
        return p

    f = jax.pmap(prog, axis_name="flat", in_axes=0)
    pk = f(X)
    return jax.tree_util.tree_map(lambda a: a[0], pk)


def _model_loss_flat(jax, ov, params, x):
    """The replicated-reference form of the toy model (full weights,
    no gathers) — same math, flat mesh."""
    import jax.numpy as jnp
    from jax import lax
    params = ov.tap_root(params)
    h = x @ params["embed"]

    def body(h, lp):
        lp = ov.grad_tap(lp)
        h = jnp.tanh(h @ lp["w"] + lp["b"])
        return h, None

    h, _ = lax.scan(body, h, params["layers"])
    return ((h * params["final_norm"]) ** 2).sum()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--data", type=int, default=2,
                    help="data-axis size (default 2)")
    ap.add_argument("--model", type=int, default=2,
                    help="model-axis size (default 2)")
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--width", type=int, default=64)
    ap.add_argument("--threshold", type=int, default=8 << 10)
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--smoke", action="store_true",
                    help="CI: tiny model, assert invariants, fast")
    args = ap.parse_args()
    if args.smoke:
        args.layers, args.width = 3, 32
        args.threshold = 2 << 10
        args.steps = 3

    D, M = args.data, args.model
    jax = _setup_jax(D * M)
    sys.path.insert(0, REPO)
    import numpy as np
    from horovod_tpu.ops.fusion import dtype_nbytes
    from horovod_tpu.optim.distributed import (make_spec_plan,
                                               sharded_tile_layout)
    from horovod_tpu.optim.precision import tree_nbytes

    params = _make_params(jax, args.layers, args.width)
    specs = _specs(jax)
    total_bytes = tree_nbytes(params)
    result = {"mesh": {"data": D, "model": M},
              "params_bytes_full": total_bytes,
              "threshold_bytes": args.threshold}

    # --- gate 1: per-chip bytes at the model-shard fraction (exact) ---
    sharded_leaf_bytes = (
        tree_nbytes(params["layers"]["w"]) // M
        + tree_nbytes(params["layers"]["b"])
        + tree_nbytes(params["embed"]) + tree_nbytes(params["final_norm"]))
    p_zero, s_zero = _run_ab(
        jax, _tx(True, None, specs, args.threshold), params, D, M,
        args.steps)
    chip_params = jax.tree_util.tree_map(
        lambda a: a[0, 0], p_zero)
    assert tree_nbytes(chip_params) == sharded_leaf_bytes, (
        tree_nbytes(chip_params), sharded_leaf_bytes)
    # exact ZeRO tile accounting: inner state == 2 adam moments on the
    # data-axis tiles of the LOCAL (model-shard) buckets + the int32
    # step count — total/(model*data) + planner padding, priced by the
    # same layout the transform tiles with
    local_shapes = _local_shapes(jax, params, M)
    plan = make_spec_plan(specs, "data", ("model",))
    layout = sharded_tile_layout(local_shapes, D,
                                 threshold_bytes=args.threshold,
                                 spec_plan=plan)
    leaves = sorted(jax.tree_util.tree_leaves_with_path(local_shapes),
                    key=lambda kv: jax.tree_util.keystr(kv[0]))
    tile_bytes = sum(
        bl.shard_numel * dtype_nbytes(str(leaves[bl.indices[0]][1].dtype))
        for bl in layout.buckets)
    chip_state = jax.tree_util.tree_map(lambda a: a[0, 0], s_zero.inner)
    expect_state = 2 * tile_bytes + 4          # adam mu+nu tiles + count
    assert tree_nbytes(chip_state) == expect_state, (
        tree_nbytes(chip_state), expect_state)
    result["per_chip"] = {
        "params_bytes": int(tree_nbytes(chip_params)),
        "inner_state_bytes": int(tree_nbytes(chip_state)),
        "state_fraction_of_full": round(
            tree_nbytes(chip_state) / (2 * total_bytes), 4),
    }

    # --- gate 2: DCN (data-hop) wire bytes, strict ring accounting ---
    # shard-fraction claim: the sharded spec-aware schedule's data hop
    # vs the same plan over full-width (replicated) operands
    wire_zero = _trace_wire(jax, _tx(True, None, specs, args.threshold,
                                     overlap=False),
                            params, D, M, True)
    wire_repl = _trace_wire(jax, _tx(True, None, None, args.threshold,
                                     overlap=False, model_axes=None),
                            params, D, M, False)
    # int8 claim on the fully-quantized staging (plain spec path: both
    # the scatter and the gather ride int8 lanes + block scales; the
    # sharded config's updates gather deliberately stays fp32, see
    # fused_reduce_scatter_tree).  Block 64: 4B/elem -> 1B + 4/64
    # scale overhead, and the n*block alignment padding stays small
    # against this bench's bucket sizes
    wire_fp32 = _trace_wire(jax, _tx(False, None, specs, args.threshold,
                                     overlap=False),
                            params, D, M, True)
    wire_int8 = _trace_wire(jax, _tx(False, "int8", specs,
                                     args.threshold, overlap=False,
                                     block=64),
                            params, D, M, True)
    result["data_hop_wire_bytes"] = {
        "replicated_fp32": wire_repl, "zero_spec_fp32": wire_zero,
        "spec_fp32": wire_fp32, "spec_int8": wire_int8,
        "int8_ratio": round(wire_fp32 / max(1, wire_int8), 2),
    }
    # the spec-aware schedule's data hop carries ~the model-shard
    # fraction of the replicated plan's bytes (replicated leaves keep
    # full width, so the bound is fractional, not exactly 1/M)
    assert wire_zero < wire_repl, result["data_hop_wire_bytes"]
    # the CI gate (docs/performance.md): >= 3.5x on the documented 2x2
    # mesh.  Other shapes keep a looser floor — the n*block alignment
    # padding grows with the data degree against this bench's small
    # buckets, which is a bench-geometry artifact, not a wire property
    assert wire_fp32 / wire_int8 >= (3.5 if (D, M) == (2, 2) else 3.0), \
        result["data_hop_wire_bytes"]

    # --- gate 3: one-program fire-gated A/B, all four configs ---
    ab = {}
    weights = {"zero": p_zero}
    for tag, kw in (("plain", dict(sharded=False, wire=None)),
                    ("int8", dict(sharded=False, wire="int8")),
                    ("int8_zero", dict(sharded=True, wire="int8"))):
        p_on, _ = _run_ab(jax, _tx(kw["sharded"], kw["wire"], specs,
                                   args.threshold), params, D, M,
                          args.steps)
        weights[tag] = p_on
        ab[tag] = "bit-identical"
    ab["zero"] = "bit-identical"
    result["fire_ab"] = ab

    # --- gate 4: spec-aware == replicated parity (allclose) ---
    p_ref = _replicated_reference(jax, params, D * M, args.threshold,
                                  args.steps)
    p_spec = jax.tree_util.tree_map(lambda a: a[0, 0],
                                    weights["plain"])
    ref_carved = {
        "embed": p_ref["embed"],
        "layers": {"w": p_ref["layers"]["w"][:, : args.width // M, :],
                   "b": p_ref["layers"]["b"]},
        "final_norm": p_ref["final_norm"],
    }
    for a, b in zip(jax.tree_util.tree_leaves(p_spec),
                    jax.tree_util.tree_leaves(ref_carved)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)
    result["replicated_parity"] = "allclose"

    print(json.dumps(result, indent=2, sort_keys=True))
    if args.smoke:
        print("bench_fsdp smoke OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())

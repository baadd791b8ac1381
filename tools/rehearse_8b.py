"""AOT compile rehearsal for BASELINE config 4 (Llama-3-8B DP, v5p-128).

One chip, or one four-chip host, cannot run the 8B workload, so this
rehearses it the AOT way: build the REAL ``llama3_8b()`` training step — dp x tp
mesh, vocab-parallel embedding/head, ZeRO-1, bf16-moment AdamW, chunked
vocab cross-entropy, full remat — over a SIMULATED 64-chip mesh
(v5p-128 = 64 chips) of virtual CPU devices, ``jax.jit(...).lower()``
it end to end (trace + StableHLO emission, no executable build), and
report the per-chip HBM the sharded train state needs, computed from
the actual shapes and NamedShardings.

Prints ONE JSON line; ``tests/test_llama.py`` runs this in a subprocess
and asserts the contract, and docs/estimators.md records the numbers.
"""

import json
import os
import sys

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=64")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402


def per_chip_bytes(tree_shapes, tree_shardings, mesh) -> int:
    """Bytes one chip holds for ``tree_shapes`` under ``tree_shardings``
    (a leaf's per-chip share is nbytes / prod(mesh axes in its spec))."""
    total = 0
    leaves_s = jax.tree_util.tree_leaves(tree_shapes)
    leaves_p = jax.tree_util.tree_leaves(
        tree_shardings, is_leaf=lambda x: hasattr(x, "spec"))
    assert len(leaves_s) == len(leaves_p), (len(leaves_s), len(leaves_p))
    for sh, nsh in zip(leaves_s, leaves_p):
        denom = 1
        for axes in nsh.spec:
            if axes is None:
                continue
            for ax in (axes if isinstance(axes, tuple) else (axes,)):
                denom *= mesh.shape[ax]
        total += sh.size * sh.dtype.itemsize // denom
    return total


def main():
    from horovod_tpu import training
    from horovod_tpu.models import llama
    from horovod_tpu.optim.precision import adamw_lp
    from horovod_tpu.parallel.mesh import MeshConfig, ParallelMesh

    dp, tp = llama.LLAMA8B_DP, llama.LLAMA8B_TP   # 64 chips = v5p-128
    seq = int(os.environ.get("REHEARSE_SEQ", "4096"))
    per_dp_batch = 1
    # the SAME configuration bench.py's llama8b_dp mode measures
    # (shared helper — rehearsal and measurement cannot drift apart)
    cfg = llama.llama3_8b_train_cfg(seq=seq)
    pmesh = ParallelMesh(MeshConfig(dp=dp, tp=tp))
    ts = training.make_llama_train_step(
        cfg, pmesh, optimizer=adamw_lp(3e-4), zero1=True)

    rng = jax.random.PRNGKey(0)
    params_s, opt_s = jax.eval_shape(ts.init_fn, rng)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params_s))

    B = per_dp_batch * dp
    tok = jax.ShapeDtypeStruct((B, seq), jnp.int32)
    lowered = ts.step_fn.lower(params_s, opt_s, tok, tok)
    hlo_bytes = len(lowered.as_text("stablehlo"))

    # per-chip steady-state HBM from the REAL shapes + shardings:
    # fp32 master params (tp-sharded; norms replicated) ...
    p_bytes = per_chip_bytes(params_s, ts.param_sharding, pmesh.mesh)
    # ... moments follow the param specs (norm moments are tp-replicated)
    # and ZeRO-1 additionally shards them over dp; non-param-shaped
    # leaves (step counters, scalars) are replicated
    pdef = jax.tree_util.tree_structure(params_s)

    def _is_param_tree(x):
        try:
            return jax.tree_util.tree_structure(x) == pdef
        except Exception:  # noqa: BLE001 - non-pytree nodes
            return False

    o_bytes = 0
    for sub in jax.tree_util.tree_leaves(opt_s, is_leaf=_is_param_tree):
        if _is_param_tree(sub):
            o_bytes += per_chip_bytes(sub, ts.param_sharding,
                                      pmesh.mesh) // dp
        else:
            o_bytes += sub.size * sub.dtype.itemsize
    # ... transient: bf16 compute copy of the tp shard + fp32 grads
    g_bytes = p_bytes                    # fp32 grads, param-sharded
    c_bytes = p_bytes // 2               # bf16 cast of the tp shard
    gib = 1 << 30

    # --- composed spec-aware plane (ISSUE 14): the same 8B geometry
    # under DistributedGradientTransform(param_specs=..., sharded_
    # update=True) with bf16 moments — tp is the model axis, dp the
    # data axis, and the per-chip moment bytes are the EXACT data-axis
    # tile sizes of the tp-local bucket layout (planner metadata, the
    # same accounting tools/bench_fsdp.py gates against the live state)
    from horovod_tpu.optim.distributed import (make_spec_plan,
                                               sharded_tile_layout)
    leaves_s = jax.tree_util.tree_leaves(params_s)
    leaves_p = jax.tree_util.tree_leaves(
        ts.param_sharding, is_leaf=lambda x: hasattr(x, "spec"))
    treedef = jax.tree_util.tree_structure(params_s)
    local_leaves, spec_leaves = [], []
    for sh, nsh in zip(leaves_s, leaves_p):
        dims = list(sh.shape)
        for d, axes in enumerate(nsh.spec):
            if axes is None:
                continue
            for ax in (axes if isinstance(axes, tuple) else (axes,)):
                dims[d] //= pmesh.mesh.shape[ax]
        local_leaves.append(jax.ShapeDtypeStruct(tuple(dims), sh.dtype))
        spec_leaves.append(nsh.spec)
    local_shapes = jax.tree_util.tree_unflatten(treedef, local_leaves)
    spec_tree = jax.tree_util.tree_unflatten(treedef, spec_leaves)
    layout = sharded_tile_layout(
        local_shapes, dp,
        spec_plan=make_spec_plan(spec_tree, "dp"))
    local_numel = sum(x.size for x in local_leaves)
    # 2 moments (mu, nu) x bf16 (2 B): replicated-DP vs tiled per chip
    mo_repl = 2 * 2 * local_numel
    mo_spec = 2 * 2 * sum(bl.shard_numel for bl in layout.buckets)

    # --- serving-side KV accounting (ISSUE 20): what one serving chip
    # holds for the decode cache at the real 8B shapes, priced exactly
    # (per-block bytes x block counts, the same ledger bench_serve's
    # --paged gates) — dense pays batch x bucket-max unconditionally;
    # paged pays ceil((len + new)/block) blocks per row
    from horovod_tpu.serving.paging import (dense_kv_nbytes,
                                            kv_block_nbytes, row_blocks)
    kv_block = 16
    kv_new = 256
    kv_batch = 8
    blk = kv_block_nbytes(cfg, kv_block)
    dense_bytes = dense_kv_nbytes(cfg, kv_batch, seq + kv_new)
    paged_at = {
        str(ln): kv_batch * row_blocks(ln, kv_new, kv_block) * blk
        for ln in (512, 1024, 2048, seq)}

    print(json.dumps({
        "ok": True,
        "n_params": int(n_params),
        "mesh": {"dp": dp, "tp": tp, "chips": dp * tp},
        "seq": seq,
        "global_batch": B,
        "stablehlo_bytes": hlo_bytes,
        "per_chip_gib": {
            "params_fp32": round(p_bytes / gib, 2),
            "opt_moments_bf16_zero1": round(o_bytes / gib, 2),
            "grads_fp32_transient": round(g_bytes / gib, 2),
            "bf16_copy_transient": round(c_bytes / gib, 2),
            "steady_plus_peak": round(
                (p_bytes + o_bytes + g_bytes + c_bytes) / gib, 2),
        },
        # ISSUE 14: the composed spec-aware path's state accounting
        # (exact planner tile bytes, not a fraction estimate) next to
        # the GSPMD zero1 number above — what the explicit gradient
        # plane holds when ZeRO tiles/quantized wire/overlap taps ride
        # the dp axis of the dp x tp mesh
        "specaware": {
            "moments_bf16_replicated_dp_bytes": mo_repl,
            "moments_bf16_zero_tiles_bytes": mo_spec,
            "state_drop_vs_replicated": round(mo_repl / mo_spec, 2),
            "per_chip_gib": round(mo_spec / gib, 3),
        },
        # ISSUE 20: serving decode-cache residency at the same shapes —
        # a batch of kv_batch rows decoding kv_new tokens from a
        # bucket_seq-token bucket.  Dense is the bucket-max buffer every
        # row pays; paged is the exact block count at the given TRUE
        # prompt length (the win grows as real lengths fall short of
        # the bucket)
        "serving_kv": {
            "block": kv_block,
            "block_nbytes": blk,
            "batch": kv_batch,
            "bucket_seq": seq,
            "max_new_tokens": kv_new,
            "dense_gib": round(dense_bytes / gib, 3),
            "paged_gib_at_len": {
                k: round(v / gib, 3) for k, v in paged_at.items()},
            "paged_fraction_at_len": {
                k: round(v / dense_bytes, 4)
                for k, v in paged_at.items()},
        },
        "v5p_hbm_gib": 95,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())

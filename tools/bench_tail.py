#!/usr/bin/env python
"""Tail-tolerant collective microbench: p99 round bound, bit-exact
parity, convergence cost, and byte conservation on the CPU mesh.

Measures what ISSUE 11 changes — whether one straggler host still sets
the DCN round time of a hierarchical fused reduce — on a virtual
(cross × local) CPU mesh (nested ``pmap`` over
``--xla_force_host_platform_device_count`` devices).  Four gates, all
asserted every run:

  * **p99 bound** (the tail claim itself): under a fixed
    ``collective.dcn`` chaos seed injecting an 800 ms arrival delay on
    one cross-group, the strict policy's round p99 tracks the injected
    delay (it waits the straggler out) while the bounded policy's p99
    stays ≤ ``deadline + ε`` — the deadline gate, not the slowest host,
    sets the round time.  The same rounds feed the stall inspector's
    straggler EWMA, which must conclusively finger the injected group.
  * **bit-exact parity**: the strict/bounded A/B runs ONE compiled
    program with a runtime ``fire`` gate (strict branch vs
    masked-bounded branch inside ``lax.cond``) — with no deadline
    firing (all-ones mask) the weights after ``--steps`` adam steps
    must be BIT-IDENTICAL across plain / sharded(-update) / int8-wire
    configs.  (Two separately compiled programs differ by XLA fusion
    ulps — the bench_overlap lesson — hence the runtime gate.)
  * **convergence cost**: a toy regression trained with a recurring
    straggler (one group excluded every third round) under ``bounded``
    and ``stale`` must keep its final loss within the documented gate
    of the strict trajectory (docs/performance.md "Tail-tolerant
    collectives").
  * **byte conservation**: ring-model transmit bytes
    (``analysis/wire.py``, ``strict=True`` accounting so an unmodeled
    primitive fails loudly) — bounded adds ONLY the pmin
    membership-agreement round over strict; stale's DCN hop rewrites
    the cross psum into a per-group all_gather at exactly G/2 the ring
    psum ratio.

    python tools/bench_tail.py               # 2x4 mesh
    python tools/bench_tail.py --smoke       # CI: 2x2, fast, asserts

Results print as JSON; see docs/performance.md "Tail-tolerant
collectives".
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CROSS, LOCAL = "tc", "tl"   # DCN / ICI axis names


def _setup_jax(n_devices: int):
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}")
    import jax
    jax.config.update("jax_platforms", "cpu")
    return jax


def _pmap2(jax, fn, G, L, in_axes):
    """Nested pmap over the (cross, local) factoring: data leading dims
    [G, L, ...]."""
    inner = jax.pmap(fn, axis_name=LOCAL, in_axes=in_axes)
    outer_axes = tuple(0 if a is not None else None for a in in_axes)
    return jax.pmap(inner, axis_name=CROSS, in_axes=outer_axes)


# ---------------------------------------------------------------------------
# gate 1: chaos-seeded p99 round bound + straggler scoring
# ---------------------------------------------------------------------------

def bench_p99(jax, G, L, rounds, delay_s, deadline_s):
    import numpy as np
    import horovod_tpu.chaos as chaos
    from horovod_tpu.ops import collectives
    from horovod_tpu.stall import StallInspector

    x = np.arange(G * L * 64, dtype=np.float32).reshape(G, L, 64)

    def reduce_fn(xs, present):
        red, _, _ = collectives.tail_allreduce_p(
            xs, CROSS, "bounded", present=present, agree_axes=(LOCAL,))
        return red
    f = _pmap2(jax, reduce_fn, G, L, in_axes=(0, None))
    f(x, np.ones(G, np.float32))   # warm the compile out of the timings

    def run(policy):
        insp = StallInspector(check_time=1e9, use_native=False)
        sched = chaos.FaultSchedule.parse(
            f"collective.dcn group=1 every=3 action=delay:{delay_s}",
            seed=11)
        chaos.install(sched)
        times = []
        try:
            for _ in range(rounds):
                t0 = time.perf_counter()
                present = collectives.tail_round(
                    "bench_tail", policy, G, deadline_s, stall=insp)
                out = f(x, np.asarray(present, np.float32))
                jax.block_until_ready(out)
                times.append(time.perf_counter() - t0)
        finally:
            chaos.uninstall()
        assert sched.fired_at("collective.dcn"), \
            "chaos seed was inert: no collective.dcn injection fired"
        return np.asarray(times), insp.straggler_scores()

    t_strict, _ = run("strict")
    t_bounded, scores = run("bounded")
    p99_strict = float(np.quantile(t_strict, 0.99))
    p99_bounded = float(np.quantile(t_bounded, 0.99))
    eps = 0.1
    # the tail claim: strict p99 tracks the injected delay, bounded p99
    # is bounded by the deadline — not by the slowest host
    assert p99_strict >= delay_s, (p99_strict, delay_s)
    assert p99_bounded <= deadline_s + eps, (p99_bounded, deadline_s)
    # the same rounds must conclusively finger the straggler
    assert scores.get(1, 0.0) > scores.get(0, 0.0) and scores[1] > 0.0, \
        scores
    return {
        "rounds": rounds, "injected_delay_s": delay_s,
        "deadline_s": deadline_s,
        "p99_strict_s": round(p99_strict, 4),
        "p99_bounded_s": round(p99_bounded, 4),
        "p50_strict_s": round(float(np.quantile(t_strict, 0.5)), 4),
        "p50_bounded_s": round(float(np.quantile(t_bounded, 0.5)), 4),
        "straggler_scores": {str(k): round(v, 4)
                             for k, v in sorted(scores.items())},
    }


# ---------------------------------------------------------------------------
# gate 2: one-program strict/bounded A/B, bit-identical weights
# ---------------------------------------------------------------------------

def _toy_data(np, G, L, dim, rows, seed=0):
    rng = np.random.default_rng(seed)
    w_true = rng.standard_normal((dim, 1)).astype(np.float32)
    X = rng.standard_normal((G, L, rows, dim)).astype(np.float32)
    y = X @ w_true + 0.01 * rng.standard_normal(
        (G, L, rows, 1)).astype(np.float32)
    return X, y


def _loss(jnp, p, xb, yb):
    pred = xb @ p["w"] + p["b"]
    return ((pred - yb) ** 2).mean()


def bench_ab(jax, G, L, steps, threshold, wire_format=None):
    """plain / int8 config: grads reduced with fused_tail_reduce_tree,
    one program whose cond arm flips strict <-> bounded."""
    import jax.numpy as jnp
    import numpy as np
    import optax
    from horovod_tpu.optim.distributed import fused_tail_reduce_tree

    dim, rows = 24, 32
    X, y = _toy_data(np, G, L, dim, rows)
    params0 = {"w": np.zeros((dim, 1), np.float32),
               "b": np.zeros((1,), np.float32)}
    tx = optax.adam(5e-2)

    def step(p, s, xb, yb, fire, present):
        g = jax.grad(lambda q: _loss(jnp, q, xb, yb))(p)

        def armed(gg):
            r, _ = fused_tail_reduce_tree(
                gg, CROSS, LOCAL, op="average", threshold_bytes=threshold,
                tail_policy="bounded", present=present,
                wire_format=wire_format)
            return r

        def boundary(gg):
            r, _ = fused_tail_reduce_tree(
                gg, CROSS, LOCAL, op="average", threshold_bytes=threshold,
                tail_policy="strict", wire_format=wire_format)
            return r

        g = jax.lax.cond(fire, armed, boundary, g)
        u, ns = tx.update(g, s, p)
        return optax.apply_updates(p, u), ns

    f = _pmap2(jax, step, G, L, in_axes=(None, None, 0, 0, None, None))
    s0 = tx.init(params0)
    ones = np.ones(G, np.float32)

    def trajectory(fire):
        p, s = params0, s0
        for _ in range(steps):
            pk, sk = f(p, s, X, y, np.asarray(fire), ones)
            for leaf in jax.tree_util.tree_leaves(pk):
                a = np.asarray(leaf).reshape(G * L, -1)
                assert (a[0] == a).all(), \
                    "replicas diverged under the tail reduce"
            p = jax.tree_util.tree_map(lambda a: a[0, 0], pk)
            s = jax.tree_util.tree_map(lambda a: a[0, 0], sk)
        return p

    p_on = trajectory(True)
    p_off = trajectory(False)
    for a, b in zip(jax.tree_util.tree_leaves(p_on),
                    jax.tree_util.tree_leaves(p_off)):
        a, b = np.asarray(a), np.asarray(b)
        assert (a == b).all(), \
            f"weights not bit-identical: max delta {np.abs(a - b).max()}"
    return {"steps": steps, "weights_bit_identical": True}


def bench_ab_sharded(jax, G, L, steps):
    """sharded config: ZeRO-style hierarchical update — psum_scatter
    over the local axis, the tail DCN stage (cond strict/bounded) on
    the 1/L chunk, adam on this worker's tile, all_gather of updated
    params — the per-chip-state composition the tail policy must not
    perturb."""
    import jax.numpy as jnp
    import numpy as np
    from horovod_tpu.ops import collectives

    dim, rows = 24, 32
    X, y = _toy_data(np, G, L, dim, rows, seed=1)
    n_param = dim + 1
    pad = (-n_param) % L
    P = n_param + pad
    lr, b1, b2, eps = 5e-2, 0.9, 0.999, 1e-8

    def split(p_flat):
        return {"w": p_flat[:dim].reshape(dim, 1),
                "b": p_flat[dim:dim + 1]}

    def step(p_flat, m, v, t, xb, yb, fire, present):
        g = jax.grad(lambda q: _loss(jnp, split(q), xb, yb))(p_flat)
        gp = jnp.concatenate([g, jnp.zeros((pad,), g.dtype)]) if pad else g
        chunk = jax.lax.psum_scatter(               # ICI stage: 1/L tile
            gp, LOCAL, scatter_dimension=0, tiled=True)

        def armed(c):
            r, _, _ = collectives.tail_allreduce_p(
                c, CROSS, "bounded", present=present, agree_axes=(LOCAL,))
            return r

        def boundary(c):
            r, _, _ = collectives.tail_allreduce_p(c, CROSS, "strict")
            return r

        chunk = jax.lax.cond(fire, armed, boundary, chunk) / (G * L)
        # adam on this worker's 1/L tile (state is tile-shaped)
        t2 = t + 1
        m2 = b1 * m + (1 - b1) * chunk
        v2 = b2 * v + (1 - b2) * chunk * chunk
        mh = m2 / (1 - b1 ** t2)
        vh = v2 / (1 - b2 ** t2)
        idx = jax.lax.axis_index(LOCAL)
        tile = jax.lax.dynamic_slice_in_dim(
            jnp.concatenate([p_flat, jnp.zeros((pad,), p_flat.dtype)])
            if pad else p_flat, idx * (P // L), P // L)
        new_tile = tile - lr * mh / (jnp.sqrt(vh) + eps)
        p_new = jax.lax.all_gather(new_tile, LOCAL, tiled=True)[:n_param]
        return p_new, m2, v2, t2

    f = _pmap2(jax, step, G, L,
               in_axes=(None, 0, 0, None, 0, 0, None, None))
    ones = np.ones(G, np.float32)
    p0 = np.zeros((n_param,), np.float32)
    m0 = np.zeros((G, L, P // L), np.float32)
    v0 = np.zeros((G, L, P // L), np.float32)

    def trajectory(fire):
        p, m, v, t = p0, m0, v0, 0
        for _ in range(steps):
            pk, m, v, tk = f(p, m, v, np.float32(t), X, y,
                             np.asarray(fire), ones)
            a = np.asarray(pk).reshape(G * L, -1)
            assert (a[0] == a).all(), "replicas diverged (sharded tail)"
            p = np.asarray(pk)[0, 0]
            t = float(np.asarray(tk)[0, 0])
        return p

    p_on, p_off = trajectory(True), trajectory(False)
    import numpy as _np
    assert (_np.asarray(p_on) == _np.asarray(p_off)).all(), \
        "sharded weights not bit-identical"
    return {"steps": steps, "weights_bit_identical": True}


# ---------------------------------------------------------------------------
# gate 3: convergence cost under a recurring straggler
# ---------------------------------------------------------------------------

#: documented rel-loss gate (docs/performance.md "Tail-tolerant
#: collectives"): a 1-in-3-rounds straggler under bounded/stale must
#: keep the toy final loss within 15% relative of the strict run.
REL_LOSS_GATE = 0.15


def bench_training(jax, G, L, steps, threshold):
    import jax.numpy as jnp
    import numpy as np
    import optax
    from horovod_tpu.optim.distributed import fused_tail_reduce_tree

    dim, rows = 32, 64
    X, y = _toy_data(np, G, L, dim, rows, seed=2)
    params0 = {"w": np.zeros((dim, 1), np.float32),
               "b": np.zeros((1,), np.float32)}
    tx = optax.adam(5e-2)

    def make_step(policy):
        def step(p, s, state, xb, yb, present):
            g = jax.grad(lambda q: _loss(jnp, q, xb, yb))(p)
            g, new_state = fused_tail_reduce_tree(
                g, CROSS, LOCAL, op="average", threshold_bytes=threshold,
                tail_policy=policy, present=present,
                tail_state=state if policy == "stale" else None,
                max_staleness=4)
            u, ns = tx.update(g, s, p)
            if new_state is None:
                new_state = state
            return optax.apply_updates(p, u), ns, new_state
        return step

    def run(policy):
        step = make_step(policy)
        # stale threads per-bucket (prev, staleness) state; shapes come
        # from a throwaway trace on the real plan (init round, ones)
        f = _pmap2(jax, step, G, L,
                   in_axes=(None, None, 0, 0, 0, None))
        p, s = params0, tx.init(params0)
        # first call initializes state inside the trace (tail_state=None
        # path needs static None) — so thread an explicit zeros state
        # built by one abstract eval
        if policy == "stale":
            # per-bucket zeros state, shaped from the same plan the
            # traced step computes (prev [G, chunk] + staleness [G] per
            # device, stacked over the [G, L] mesh for pmap threading)
            from horovod_tpu.optim.distributed import (_plan_buckets,
                                                       _tree_leaves_sorted)
            from horovod_tpu.ops.fusion import pad_to_multiple
            leaves, names, _o = _tree_leaves_sorted(params0)
            buckets, _s = _plan_buckets(leaves, names, "average", 1.0,
                                        1.0, threshold,
                                        tail_policy="stale")
            state = tuple(
                (np.zeros((G, L, G,
                           pad_to_multiple(sum(leaves[i].size
                                               for i in b), L) // L),
                          np.float32),
                 np.zeros((G, L, G), np.int32))
                for b in buckets)
        else:
            state = tuple()
        losses = []
        for k in range(steps):
            present = np.ones(G, np.float32)
            if policy != "strict" and k % 3 == 2:
                present[G - 1] = 0.0   # the recurring straggler
            p_k, s_k, state = f(p, s, state, X, y, present)
            p = jax.tree_util.tree_map(lambda a: a[0, 0], p_k)
            s = jax.tree_util.tree_map(lambda a: a[0, 0], s_k)
        flat = [float(_loss(jnp, {k2: jnp.asarray(v) for k2, v in p.items()},
                            X[i, j], y[i, j]))
                for i in range(G) for j in range(L)]
        return p, float(np.mean(flat))

    _, loss_strict = run("strict")
    out = {"steps": steps, "final_loss_strict": round(loss_strict, 6)}
    for policy in ("bounded", "stale"):
        _, loss_p = run(policy)
        rel = abs(loss_p - loss_strict) / max(loss_strict, 1e-9)
        assert rel < REL_LOSS_GATE, (policy, loss_p, loss_strict, rel)
        out[f"final_loss_{policy}"] = round(loss_p, 6)
        out[f"rel_delta_{policy}"] = round(rel, 4)
    out["rel_loss_gate"] = REL_LOSS_GATE
    return out


# ---------------------------------------------------------------------------
# byte conservation: the tail adds only the agreement round
# ---------------------------------------------------------------------------

def bench_bytes(jax, G, L, threshold):
    import jax.numpy as jnp
    from horovod_tpu.analysis.schedule import trace_schedule
    from horovod_tpu.analysis.wire import (prim_counts,
                                           ring_transmit_bytes,
                                           schedule_transmit_bytes)
    from horovod_tpu.optim.distributed import fused_tail_reduce_tree

    sds = jax.ShapeDtypeStruct
    spec = {"w": sds((96, 8), jnp.float32), "b": sds((33,), jnp.float32)}
    env = [(CROSS, G), (LOCAL, L)]

    def step_for(policy):
        def step(g):
            r, _ = fused_tail_reduce_tree(
                g, CROSS, LOCAL, op="average", threshold_bytes=threshold,
                tail_policy=policy,
                present=(None if policy == "strict"
                         else jnp.ones((G,), jnp.float32)),
                max_staleness=4)
            return r
        return step

    scheds = {p: trace_schedule(step_for(p), (spec,), axis_env=env,
                                entry=f"bench_tail_{p}")
              for p in ("strict", "bounded", "stale")}
    sizes = dict(env)
    # strict accounting: an unmodeled primitive in any tail schedule
    # must fail the gate loudly, never be silently mis-priced
    total = {p: schedule_transmit_bytes(s, strict=True)
             for p, s in scheds.items()}
    agree = {p: sum(ring_transmit_bytes(r, sizes, strict=True)
                    for r in s.records if r.prim == "pmin")
             for p, s in scheds.items()}
    # bounded = strict + the pmin membership agreement, nothing else
    assert agree["strict"] == 0, prim_counts(scheds["strict"])
    assert agree["bounded"] > 0, prim_counts(scheds["bounded"])
    assert total["bounded"] == total["strict"] + agree["bounded"], \
        (total, agree)
    # stale rewrites the DCN psum into a per-group all_gather: ring
    # cost G/2 x the psum's on the cross axis (exact for even G)
    dcn_strict = schedule_transmit_bytes(scheds["strict"], sizes,
                                         axis_filter=CROSS, strict=True)
    dcn_stale = schedule_transmit_bytes(scheds["stale"], sizes,
                                        axis_filter=CROSS, strict=True)
    agree_c = sum(ring_transmit_bytes(r, sizes, strict=True)
                  for r in scheds["stale"].records
                  if r.prim == "pmin" and r.axes == [CROSS])
    assert dcn_stale - agree_c == dcn_strict * G // 2, \
        (dcn_stale, agree_c, dcn_strict, G)
    # and no stale schedule may carry a cross-axis psum at all
    assert not any(r.prim == "psum" and CROSS in r.axes
                   for r in scheds["stale"].records), \
        prim_counts(scheds["stale"])
    return {
        "prims": {p: prim_counts(s) for p, s in scheds.items()},
        "total_bytes": total,
        "agreement_bytes_bounded": agree["bounded"],
        "dcn_bytes_strict": dcn_strict,
        "dcn_bytes_stale": dcn_stale,
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--devices", type=int, default=8,
                    help="CPU mesh size (default 8 -> 2x4 cross x local)")
    ap.add_argument("--groups", type=int, default=2,
                    help="cross (DCN) groups (default 2)")
    ap.add_argument("--rounds", type=int, default=24,
                    help="p99 sample rounds (default 24)")
    ap.add_argument("--delay", type=float, default=0.8,
                    help="injected straggler arrival delay, seconds")
    ap.add_argument("--deadline", type=float, default=0.25,
                    help="bounded-policy deadline, seconds")
    ap.add_argument("--steps", type=int, default=30,
                    help="training steps for the A/B + convergence gates")
    ap.add_argument("--threshold", type=int, default=512,
                    help="fusion threshold bytes (small: multi-bucket)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI: 2x2 mesh, fewer rounds/steps, asserts only")
    args = ap.parse_args()

    if args.smoke:
        args.devices, args.rounds, args.steps = 4, 9, 12

    jax = _setup_jax(args.devices)
    sys.path.insert(0, REPO)
    G = args.groups
    L = args.devices // G
    assert G * L == args.devices, (G, args.devices)

    result = {
        "mesh": {"cross": G, "local": L},
        "p99": bench_p99(jax, G, L, args.rounds, args.delay,
                         args.deadline),
        "ab_plain": bench_ab(jax, G, L, args.steps, args.threshold),
        "ab_int8": bench_ab(jax, G, L, args.steps, args.threshold,
                            wire_format="int8"),
        "ab_sharded": bench_ab_sharded(jax, G, L, args.steps),
        "training": bench_training(jax, G, L, args.steps,
                                   args.threshold),
        "bytes": bench_bytes(jax, G, L, args.threshold),
    }
    print(json.dumps(result, indent=2, sort_keys=True))
    if args.smoke:
        print("bench_tail smoke OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())

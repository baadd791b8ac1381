"""Collective-schedule extraction from jaxprs (hvdsched; HVD210/HVD211).

The fused-psum plan a compiled step issues is the framework's most
safety-critical invariant: every replica must execute the same
collectives, in the same order, over the same axes — and the next wave
of perf work (ZeRO-style sharded updates, per-bucket compressed
collectives, async bucket dispatch; ROADMAP items 1–3) rewrites exactly
that plan.  This module makes the plan a *reviewable artifact*: it
traces a step function to a jaxpr **on CPU** (no devices, no mesh — an
``axis_env`` stands in for the hardware), walks the jaxpr through every
``pjit``/``scan``/``cond``/``while``/custom-derivative sub-jaxpr, and
emits the ordered collective records as stable JSON:

    (primitive, axis names, operand shapes/dtypes, sub-jaxpr path,
     fusion-bucket id, primitive params)

The fusion-bucket id rides the jaxpr's name stack: ``fused_reduce_tree``
traces each bucket under ``jax.named_scope("hvd_bucket<i>")``.

Two checks ride on top:

* **snapshot check (HVD211)** — ``tests/schedules/*.json`` records the
  schedule of every builtin entry point; ``tools/hvdsched --check``
  re-traces and diffs, so any change to the fused-psum plan (bucket
  order, threshold semantics, a new collective) is an explicit,
  reviewed snapshot update — and an accidental one fails CI.
* **consistency check (HVD210)** — the *canonical* schedule (shapes and
  axis sizes erased) must be identical across mesh sizes and any other
  configuration axis: a schedule that varies with rank or world size
  deadlocks the compiled programs against each other.

jax (and the framework's runtime deps) are imported lazily: importing
``horovod_tpu.analysis`` alone still costs only the standard library.
"""

from __future__ import annotations

import dataclasses
import difflib
import json
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from .report import Finding

#: jaxpr primitives that lower to cross-replica communication.
COLLECTIVE_PRIMS = frozenset({
    "psum", "pmax", "pmin", "all_gather", "all_to_all", "reduce_scatter",
    "ppermute", "pbroadcast", "psum_scatter",
})

#: eqn params recorded verbatim (JSON-serializable, order-stable).
#: ``axis_size`` is recorded but ERASED from the canonical form — it
#: legitimately varies with the mesh.
_RECORDED_PARAMS = (
    "axis_index_groups", "perm", "all_gather_dimension",
    "scatter_dimension", "split_axis", "concat_axis", "tiled",
    "axis_size",
)

_BUCKET_RE = re.compile(r"hvd_bucket(\d+)")

#: Snapshot format version (bump on any JSON layout change).
FORMAT = 1


# ---------------------------------------------------------------------------
# schedule model
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CollectiveRecord:
    """One collective in trace order."""
    index: int
    prim: str
    axes: List[str]
    inputs: List[str]            # "float32[8x16]" aval strings
    outputs: List[str]
    path: str                    # sub-jaxpr context, "" = top level
    bucket: Optional[int]        # fusion bucket id from the name stack
    params: Dict[str, Any]

    def as_dict(self) -> dict:
        return {"index": self.index, "prim": self.prim, "axes": self.axes,
                "inputs": self.inputs, "outputs": self.outputs,
                "path": self.path, "bucket": self.bucket,
                "params": self.params}

    def canonical(self) -> Tuple:
        """Shape-and-mesh-erased identity for HVD210 comparisons."""
        params = {k: v for k, v in self.params.items()
                  if k not in ("axis_size", "perm")}
        return (self.prim, tuple(self.axes), self.path, self.bucket,
                tuple(sorted((k, json.dumps(v)) for k, v in params.items())))


@dataclasses.dataclass
class Schedule:
    entry: str
    axis_env: List[Tuple[str, int]]
    records: List[CollectiveRecord]

    def to_json(self) -> str:
        payload = {
            "format": FORMAT,
            "entry": self.entry,
            "axis_env": [[n, int(s)] for n, s in self.axis_env],
            "records": [r.as_dict() for r in self.records],
        }
        return json.dumps(payload, indent=2, sort_keys=True) + "\n"

    @classmethod
    def from_json(cls, text: str) -> "Schedule":
        data = json.loads(text)
        if int(data.get("format", -1)) != FORMAT:
            raise ValueError(
                f"schedule snapshot format {data.get('format')} != "
                f"supported format {FORMAT}; re-record with "
                f"tools/hvdsched --update")
        records = [CollectiveRecord(
            index=r["index"], prim=r["prim"], axes=list(r["axes"]),
            inputs=list(r["inputs"]), outputs=list(r["outputs"]),
            path=r["path"], bucket=r["bucket"],
            params=dict(r["params"])) for r in data["records"]]
        return cls(entry=data["entry"],
                   axis_env=[(n, int(s)) for n, s in data["axis_env"]],
                   records=records)

    def canonical(self) -> List[Tuple]:
        return [r.canonical() for r in self.records]


# ---------------------------------------------------------------------------
# jaxpr walk
# ---------------------------------------------------------------------------

def _aval_str(aval) -> str:
    dtype = getattr(aval, "dtype", None)
    shape = getattr(aval, "shape", None)
    if dtype is None or shape is None:
        return str(aval)
    return f"{dtype.name}[{'x'.join(str(int(d)) for d in shape)}]"


def _axis_names(eqn) -> List[str]:
    raw = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if not isinstance(raw, (tuple, list)):
        raw = (raw,)
    return [str(a) for a in raw if isinstance(a, str)]


def _bucket_of(eqn) -> Optional[int]:
    try:
        stack = str(eqn.source_info.name_stack)
    except Exception:  # noqa: BLE001 - source info is best-effort
        return None
    m = _BUCKET_RE.search(stack)
    return int(m.group(1)) if m else None


def _jsonable(value) -> Any:
    if isinstance(value, (tuple, list)):
        return [_jsonable(v) for v in value]
    if isinstance(value, (str, int, float, bool)) or value is None:
        return value
    return str(value)


def _sub_jaxprs(eqn) -> List[Tuple[str, Any]]:
    """(context label, inner jaxpr) for every jaxpr-valued param, in a
    deterministic order.  Duck-typed — no jax import at module scope:
    a ClosedJaxpr has ``.jaxpr``, a Jaxpr has ``.eqns``."""
    out: List[Tuple[str, Any]] = []
    prim = eqn.primitive.name
    for key in sorted(eqn.params):
        val = eqn.params[key]
        candidates: List[Tuple[str, Any]] = []
        if hasattr(val, "eqns") or hasattr(val, "jaxpr"):
            candidates.append(("", val))
        elif isinstance(val, (tuple, list)):
            for i, v in enumerate(val):
                if hasattr(v, "eqns") or hasattr(v, "jaxpr"):
                    candidates.append((f"[{i}]", v))
        for suffix, v in candidates:
            inner = v.jaxpr if hasattr(v, "jaxpr") else v
            label = f"{prim}:{key}{suffix}"
            if prim in ("pjit", "jit"):
                name = eqn.params.get("name")
                if name:
                    label = f"pjit<{name}>"
            out.append((label, inner))
    return out


def _walk(jaxpr, path: str, records: List[CollectiveRecord]) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in COLLECTIVE_PRIMS:
            params = {k: _jsonable(eqn.params[k])
                      for k in _RECORDED_PARAMS if k in eqn.params}
            records.append(CollectiveRecord(
                index=len(records), prim=name, axes=_axis_names(eqn),
                inputs=[_aval_str(v.aval) for v in eqn.invars],
                outputs=[_aval_str(v.aval) for v in eqn.outvars],
                path=path, bucket=_bucket_of(eqn), params=params))
        for label, inner in _sub_jaxprs(eqn):
            _walk(inner, f"{path}/{label}" if path else label, records)


def trace_schedule(fn, example_args: Sequence,
                   axis_env: Sequence[Tuple[str, int]] = (),
                   entry: str = "<fn>") -> Schedule:
    """Trace ``fn(*example_args)`` to a jaxpr on CPU and extract its
    collective schedule.  ``example_args`` may be arrays or
    ``jax.ShapeDtypeStruct``s (pytrees of either)."""
    import jax
    closed = jax.make_jaxpr(
        fn, axis_env=[(n, int(s)) for n, s in axis_env])(*example_args)
    records: List[CollectiveRecord] = []
    _walk(closed.jaxpr, "", records)
    return Schedule(entry=entry, axis_env=list(axis_env), records=records)


# ---------------------------------------------------------------------------
# diffs and checks
# ---------------------------------------------------------------------------

def diff_schedules(expected: Schedule, actual: Schedule) -> List[str]:
    """Human-readable unified diff of two schedules' JSON forms
    (empty when identical)."""
    exp, act = expected.to_json().splitlines(), actual.to_json().splitlines()
    return list(difflib.unified_diff(
        exp, act, fromfile=f"expected/{expected.entry}",
        tofile=f"actual/{actual.entry}", lineterm=""))


def check_snapshot(snapshot_path: str, actual: Schedule) -> List[Finding]:
    """HVD211 when ``actual`` drifted from the committed snapshot."""
    try:
        with open(snapshot_path, "r", encoding="utf-8") as f:
            expected = Schedule.from_json(f.read())
    except FileNotFoundError:
        return [Finding("HVD211", snapshot_path, 1, 0,
                        f"no committed snapshot for entry "
                        f"'{actual.entry}' — record one with "
                        f"tools/hvdsched --update")]
    except (ValueError, KeyError) as exc:
        return [Finding("HVD211", snapshot_path, 1, 0,
                        f"unreadable snapshot: {exc}")]
    diff = diff_schedules(expected, actual)
    if not diff:
        return []
    head = next((l for l in diff if l.startswith(("+", "-"))
                 and not l.startswith(("+++", "---"))), "")
    return [Finding("HVD211", snapshot_path, 1, 0,
                    f"collective schedule for entry '{actual.entry}' "
                    f"drifted from its snapshot ({len(expected.records)} "
                    f"-> {len(actual.records)} records; first change: "
                    f"{head.strip()!r}) — intentional changes are "
                    f"re-recorded with tools/hvdsched --update")]


def check_consistency(variants: Sequence[Tuple[str, Schedule]]
                      ) -> List[Finding]:
    """HVD210 when any variant's canonical (shape/mesh-erased) schedule
    differs from the first — the cross-configuration invariant."""
    findings: List[Finding] = []
    if not variants:
        return findings
    base_label, base = variants[0]
    base_canon = base.canonical()
    for label, sched in variants[1:]:
        canon = sched.canonical()
        if canon == base_canon:
            continue
        detail = f"{len(base_canon)} vs {len(canon)} collectives"
        for i, (a, b) in enumerate(zip(base_canon, canon)):
            if a != b:
                detail = (f"record {i}: {a[0]} over {a[1]} vs "
                          f"{b[0]} over {b[1]}")
                break
        findings.append(Finding(
            "HVD210", base.entry, 1, 0,
            f"collective schedule differs between configuration "
            f"'{base_label}' and '{label}' ({detail}); every replica "
            f"must issue the same collectives in the same order, or the "
            f"compiled programs deadlock against each other"))
    return findings


# ---------------------------------------------------------------------------
# builtin entry points: the framework's in-jit bucketed reduction path
# ---------------------------------------------------------------------------

_AXIS = "workers"
#: Small threshold so the representative gradient pytree splits into
#: multiple buckets — the snapshot then pins bucket ORDER, not just count.
_THRESHOLD = 1024


def _grads_spec():
    """Representative mixed-dtype gradient pytree (ShapeDtypeStructs:
    nothing is materialized).  Sized so float32 splits across two
    buckets at ``_THRESHOLD`` while bfloat16 fuses into one."""
    import jax
    import jax.numpy as jnp
    sds = jax.ShapeDtypeStruct
    return {
        "dense/bias": sds((16,), jnp.float32),
        "dense/kernel": sds((8, 16), jnp.float32),
        "embed/table": sds((32, 8), jnp.bfloat16),
        "head/bias": sds((4,), jnp.bfloat16),
        "head/kernel": sds((64, 4), jnp.float32),
    }


def _entry_fused_reduce():
    """The in-jit fusion-buffer path: one psum per planned bucket."""
    from ..optim.distributed import fused_reduce_tree

    def step(grads):
        return fused_reduce_tree(grads, _AXIS, op="average",
                                 threshold_bytes=_THRESHOLD)
    return step, (_grads_spec(),)


def _entry_distopt_step():
    """A full DistributedOptimizer update (optax adam inner): the
    schedule users actually compile."""
    import jax
    import jax.numpy as jnp
    import optax
    from ..optim.distributed import DistributedOptimizer

    # sharded_update and wire_format pinned off: snapshots must not flip
    # with the operator's HOROVOD_SHARDED_UPDATE / HOROVOD_COMPRESSION
    # env (each rewrite has its own entry)
    tx = DistributedOptimizer(optax.adam(1e-3), axis_name=_AXIS,
                              threshold_bytes=_THRESHOLD,
                              sharded_update=False, wire_format="none")
    spec = _grads_spec()
    params = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), spec)
    state = tx.init(params)

    def step(grads, params):
        updates, _ = tx.update(grads, state, params)
        return updates
    return step, (spec, spec)


def _entry_jit_fused_reduce():
    """fused_reduce_tree under jax.jit: pins that the walk descends
    into pjit sub-jaxprs (the schedule must not go dark under jit)."""
    import jax
    from ..optim.distributed import fused_reduce_tree

    @jax.jit
    def inner(grads):
        return fused_reduce_tree(grads, _AXIS, op="sum",
                                 threshold_bytes=_THRESHOLD)

    def step(grads):
        return inner(grads)
    return step, (_grads_spec(),)


def _entry_sharded_distopt_step():
    """The ZeRO-style sharded step (HOROVOD_SHARDED_UPDATE): per bucket
    reduce_scatter → 1/N inner update → all_gather, never a full-gradient
    psum (arXiv:2004.13336; ROADMAP item 1)."""
    import optax
    from ..optim.distributed import DistributedOptimizer

    tx = DistributedOptimizer(optax.adam(1e-3), axis_name=_AXIS,
                              threshold_bytes=_THRESHOLD,
                              sharded_update=True, wire_format="none")
    spec = _grads_spec()

    def step(grads, params):
        # the sharded optimizer state is per-worker (1/N bucket tiles),
        # so init runs INSIDE the mapped program, like real sharded
        # steps do; init issues no collectives, so the schedule is the
        # update's reduce_scatter/all_gather plan alone
        state = tx.init(params)
        updates, _ = tx.update(grads, state, params)
        return updates
    return step, (spec, spec)


def _entry_quantized_distopt_step():
    """The quantized-wire step (HOROVOD_COMPRESSION=int8): per bucket the
    full-width psum is rewritten into quantize → all_to_all int8 tiles +
    fp32 scales → fp32 accumulate → all_gather quantized tiles
    (EQuARX-class staging, error feedback in _DistState.residual;
    ROADMAP item 2).  The snapshot pins the wire dtype: int8 avals in
    the exchange records ARE the compressed-bytes claim."""
    import optax
    from ..optim.distributed import DistributedOptimizer

    # explicit format + block so the snapshot cannot flip with the
    # operator's HOROVOD_COMPRESSION / block-size env; block 16 keeps
    # the tiny representative pytree multi-block
    tx = DistributedOptimizer(optax.adam(1e-3), axis_name=_AXIS,
                              threshold_bytes=_THRESHOLD,
                              sharded_update=False, wire_format="int8",
                              wire_block_size=16)
    spec = _grads_spec()

    def step(grads, params):
        # the error-feedback residual is per-worker state carried in
        # _DistState, so init runs inside the traced program; it issues
        # no collectives of its own
        state = tx.init(params)
        updates, _ = tx.update(grads, state, params)
        return updates
    return step, (spec, spec)


#: toy scanned-model geometry for the overlapped entry (layers, width,
#: vocab rows) — small enough to trace fast, deep enough that the
#: backward scan carries multiple per-layer dispatches.
_OVERLAP_L, _OVERLAP_D, _OVERLAP_V = 3, 8, 5


def _overlap_params_spec():
    """Representative scanned-model param pytree: a stacked fp32+bf16
    layer stack (two buckets per layer at ``_THRESHOLD``) plus
    non-scanned root leaves (embed, final_norm)."""
    import jax
    import jax.numpy as jnp
    sds = jax.ShapeDtypeStruct
    L, D, V = _OVERLAP_L, _OVERLAP_D, _OVERLAP_V
    return {
        "embed": sds((V, D), jnp.float32),
        "layers": {
            "b": sds((L, D), jnp.float32),
            "s": sds((L, D), jnp.bfloat16),
            "w": sds((L, D, D), jnp.float32),
        },
        "final_norm": sds((D,), jnp.float32),
    }


def _entry_overlapped_distopt_step():
    """The overlapped-dispatch step (HOROVOD_OVERLAP, ROADMAP item 3):
    the scanned toy model's grad taps fire each layer's fusion buckets
    INSIDE the backward scan (records sit in a scan sub-jaxpr path, in
    reverse layer order structurally), and the non-scanned root leaves
    reduce at the end of backprop — no post-backprop fused block.  The
    snapshot's record positions ARE the overlap claim."""
    import jax
    import jax.numpy as jnp
    import optax
    from ..optim import overlap as _ov
    from ..optim.distributed import DistributedOptimizer

    # overlap pinned on, everything else pinned off/none: the snapshot
    # must not flip with the operator's env (each rewrite has its own
    # entry)
    tx = DistributedOptimizer(optax.adam(1e-3), axis_name=_AXIS,
                              threshold_bytes=_THRESHOLD,
                              sharded_update=False, wire_format="none",
                              overlap=True)

    def model_loss(params, x):
        params = _ov.tap_root(params)
        h = x @ params["embed"]

        def body(h, lp):
            lp = _ov.grad_tap(lp)
            return (jnp.tanh(h @ lp["w"] + lp["b"])
                    * lp["s"].astype(h.dtype), None)

        h, _ = jax.lax.scan(body, h, params["layers"])
        return (h * params["final_norm"]).sum()

    def step(params, x):
        # per-step state init inside the traced program (init issues no
        # collectives); the context arms the model taps for this trace
        state = tx.init(params)
        with _ov.overlapped_backprop(tx):
            _loss, grads = jax.value_and_grad(model_loss)(params, x)
        updates, _ = tx.update(grads, state, params)
        return updates

    spec = _overlap_params_spec()
    x = jax.ShapeDtypeStruct((2, _OVERLAP_V), jnp.float32)
    return step, (spec, x)


def _entry_health_distopt_step():
    """The health-tapped step (HOROVOD_HEALTH_TAPS; ISSUE 13): the
    per-bucket numerics taps are LOCAL reductions (no collectives of
    their own), but the divergence sentinel adds one ``all_gather`` of
    the per-bucket param/opt-state checksum vector under its cadence
    ``cond`` — that gather, and nothing else, is the schedule delta vs
    the plain ``distopt_step`` entry.  health pinned ON with
    ``health_check_every=1`` (env-independent: an explicit ``health=``
    wins over HOROVOD_HEALTH_TAPS, and the first step's count=1 takes
    the sentinel branch), everything else pinned off."""
    import jax
    import jax.numpy as jnp
    import optax
    from ..optim.distributed import DistributedOptimizer

    tx = DistributedOptimizer(optax.adam(1e-3), axis_name=_AXIS,
                              threshold_bytes=_THRESHOLD,
                              sharded_update=False, wire_format="none",
                              health=True, health_check_every=1)
    spec = _grads_spec()
    params = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), spec)
    state = tx.init(params)

    def step(grads, params):
        updates, _ = tx.update(grads, state, params)
        return updates
    return step, (spec, spec)


#: fixed model axis of the spec-aware (fsdp) entry: the consistency
#: check varies the DATA axis through ``_AXIS`` — mesh shapes 2x2 and
#: 4x2 — while the model-shard degree stays 2.
_FSDP_MODEL = 2


def _fsdp_grads_spec():
    """Representative spec-aware gradient pytree: LOCAL (model-shard)
    shapes for the sharded leaves, full shapes for the replicated ones,
    in both dtypes — so the plan carries a sharded and a replicated
    bucket per dtype (mixed-spec leaves must never fuse)."""
    import jax
    import jax.numpy as jnp
    sds = jax.ShapeDtypeStruct
    return {
        # full (8, 16) sharded dim0 over hvd_model=2 -> local (4, 16)
        "dense/kernel": sds((4, 16), jnp.float32),
        "dense/bias": sds((16,), jnp.float32),
        # full (32, 8) sharded dim0 -> local (16, 8)
        "embed/table": sds((16, 8), jnp.bfloat16),
        "head/bias": sds((4,), jnp.bfloat16),
        # full (64, 4) sharded dim1 -> local (64, 2)
        "head/kernel": sds((64, 2), jnp.float32),
    }


def _entry_fsdp_distopt_step():
    """The mesh-axis-aware composed step (ISSUE 14): param_specs over a
    2-D (data x model) mesh + ZeRO sharded update.  Model-sharded
    buckets reduce-scatter their LOCAL shard over the data axis alone —
    no model-axis collective, no full-width gradient anywhere;
    replicated buckets psum over the model axis first, then tile over
    data; every bucket's updates all_gather over data only.  Specs and
    model_axes pinned explicitly (env-independent: the snapshot must
    not flip with HOROVOD_MODEL_AXES or the mesh context)."""
    import optax
    from jax.sharding import PartitionSpec as P
    from ..optim.distributed import DistributedOptimizer

    specs = {
        "dense/kernel": P("hvd_model"),
        "dense/bias": P(),
        "embed/table": P("hvd_model"),
        "head/bias": P(),
        "head/kernel": P(None, "hvd_model"),
    }
    tx = DistributedOptimizer(optax.adam(1e-3), axis_name=_AXIS,
                              threshold_bytes=_THRESHOLD,
                              sharded_update=True, wire_format="none",
                              param_specs=specs,
                              model_axes=("hvd_model",))
    spec = _fsdp_grads_spec()

    def step(grads, params):
        # 1/N-tile state init runs inside the mapped program (issues no
        # collectives); grads arrive as the locally-owned shards,
        # pre-reduced over the model axis by the model's transposes
        state = tx.init(params)
        updates, _ = tx.update(grads, state, params)
        return updates
    return step, (spec, spec), (("hvd_model", _FSDP_MODEL),)


#: fixed local (ICI) axis of the hierarchical tail entry: the
#: consistency check varies the CROSS (DCN) axis — the one the tail
#: policy rewrites — through ``_AXIS``.
_TAIL_LOCAL = 2


def _entry_tail_distopt_step():
    """The tail-tolerant hierarchical step (HOROVOD_TAIL_POLICY; ISSUE
    11, OptiReduce arXiv:2310.06993): per bucket psum_scatter over the
    local (ICI) axis, then the REWRITTEN DCN stage — a pmin
    membership-agreement round over the mask plus an all_gather of
    per-group chunk contributions (the transpose-allreduce shape that
    makes a missing host's slot substitutable), never a cross-group
    psum — then the local all_gather.  Policy pinned to ``stale`` (the
    maximally rewritten schedule; ``bounded`` keeps the psum shape and
    is pinned by tests/test_tail.py), mask/state initialized inside the
    traced step so the snapshot cannot flip with the operator's
    HOROVOD_TAIL_* env."""
    import jax
    import jax.numpy as jnp
    import optax
    from ..optim.distributed import fused_tail_reduce_tree

    spec = _grads_spec()
    tx = optax.adam(1e-3)

    def step(grads, params):
        present = jnp.ones((jax.lax.axis_size(_AXIS),), jnp.float32)
        reduced, _state = fused_tail_reduce_tree(
            grads, _AXIS, "hvd_local", op="average",
            threshold_bytes=_THRESHOLD, tail_policy="stale",
            present=present, max_staleness=3)
        state = tx.init(params)
        updates, _ = tx.update(reduced, state, params)
        return updates
    return step, (spec, spec), (("hvd_local", _TAIL_LOCAL),)


def _entry_serve_forward_step():
    """The serving data path (ISSUE 15): one batched ragged KV-cache
    decode step (prefill + per-row-positioned greedy decode scan) of
    the llama family, traced under the worker mesh axis.  Serving is
    pure data parallelism — a forward must NEVER negotiate a gradient
    collective (a straggling replica must stall only its own leases,
    and a worker joining or leaving mid-traffic must not deadlock
    peers) — so the pinned schedule is EMPTY: a regression that routes
    serving through the gradient plane (a stray psum from a reused
    training step, a health tap's sentinel gather) adds records and
    fails HVD211 structurally."""
    import jax
    import jax.numpy as jnp
    from ..models import llama
    from ..models.generate import batched_greedy_decode

    cfg = llama.tiny(vocab=64, seq=32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))

    def step(tokens, lengths):
        return batched_greedy_decode(params, cfg, tokens, lengths,
                                     max_new_tokens=4, max_len=20)

    sds = jax.ShapeDtypeStruct
    return step, (sds((2, 16), jnp.int32), sds((2,), jnp.int32))


#: fixed model axis of the mesh-sliced serving entry: the consistency
#: check varies the worker axis through ``_AXIS`` (unused by the step,
#: like serve_forward_step) while the shard degree stays 2.
_SERVE_MP = 2


def _entry_serve_mp_forward_step():
    """The model-parallel serving data path (ISSUE 20): the same
    batched ragged decode as ``serve_forward_step``, but the weights
    arrive as mesh-slice local shards and are ``spec_all_gather``ed
    over the model axis inside the step (serving/worker.py
    MeshSlicedForward).  The pinned schedule contains ONLY the spec
    gather hops — weight movement, never gradient movement.  The
    ``serve_forward_step`` empty-schedule pin generalizes: a gradient
    collective appearing here (a stray psum from a reused training
    step, a health tap riding the serving mesh) changes the record set
    and fails HVD211 structurally, exactly like a non-empty schedule
    would fail the DP entry.  Specs come from ``fsdp_param_specs`` —
    serving shards the same way training's FSDP path does, so the
    snapshot also pins that the two planes agree on what a shard is."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from ..models import llama
    from ..models.generate import batched_greedy_decode
    from ..training import fsdp_param_specs, spec_all_gather

    cfg = llama.tiny(vocab=64, seq=32)
    shapes = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    specs = fsdp_param_specs(shapes, _SERVE_MP, axis="hvd_serve_mp")

    def local_sds(spec, leaf):
        dims = list(leaf.shape)
        for dim, entry in enumerate(spec):
            axes = entry if isinstance(entry, tuple) else (entry,)
            if "hvd_serve_mp" in axes:
                dims[dim] //= _SERVE_MP
                break
        return jax.ShapeDtypeStruct(tuple(dims), leaf.dtype)

    shards = jax.tree_util.tree_map(local_sds, specs, shapes,
                                    is_leaf=lambda x: isinstance(x, P))

    def step(shards, tokens, lengths):
        full = spec_all_gather(shards, specs, "hvd_serve_mp")
        return batched_greedy_decode(full, cfg, tokens, lengths,
                                     max_new_tokens=4, max_len=20)

    sds = jax.ShapeDtypeStruct
    return (step,
            (shards, sds((2, 16), jnp.int32), sds((2,), jnp.int32)),
            (("hvd_serve_mp", _SERVE_MP),))


#: entry name -> builder returning (fn, example_args) or
#: (fn, example_args, extra_axes): ``extra_axes`` extends the trace's
#: axis_env past the varied ``_AXIS`` (hierarchical entries need a
#: second, fixed axis alongside the one the consistency check sweeps).
BUILTIN_ENTRIES = {
    "fused_reduce": _entry_fused_reduce,
    "distopt_step": _entry_distopt_step,
    "jit_fused_reduce": _entry_jit_fused_reduce,
    "sharded_distopt_step": _entry_sharded_distopt_step,
    "quantized_distopt_step": _entry_quantized_distopt_step,
    "overlapped_distopt_step": _entry_overlapped_distopt_step,
    "tail_distopt_step": _entry_tail_distopt_step,
    "health_distopt_step": _entry_health_distopt_step,
    "fsdp_distopt_step": _entry_fsdp_distopt_step,
    "serve_forward_step": _entry_serve_forward_step,
    "serve_mp_forward_step": _entry_serve_mp_forward_step,
}

#: Mesh sizes the consistency check traces every entry at (HVD210).
_CONSISTENCY_SIZES = (2, 4)


def builtin_schedule(name: str, axis_size: int = 2) -> Schedule:
    built = BUILTIN_ENTRIES[name]()
    fn, args = built[0], built[1]
    extra_axes = built[2] if len(built) > 2 else ()
    return trace_schedule(
        fn, args,
        axis_env=[(_AXIS, axis_size)] + [(n, int(s))
                                         for n, s in extra_axes],
        entry=name)


def snapshot_dir() -> str:
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(repo, "tests", "schedules")


def snapshot_path(name: str, directory: Optional[str] = None) -> str:
    return os.path.join(directory or snapshot_dir(), f"{name}.json")


def check_builtin_snapshots(directory: Optional[str] = None,
                            entries: Optional[Iterable[str]] = None
                            ) -> List[Finding]:
    findings: List[Finding] = []
    for name in (entries or sorted(BUILTIN_ENTRIES)):
        findings.extend(check_snapshot(
            snapshot_path(name, directory), builtin_schedule(name)))
    return findings


def check_builtin_consistency(entries: Optional[Iterable[str]] = None
                              ) -> List[Finding]:
    findings: List[Finding] = []
    for name in (entries or sorted(BUILTIN_ENTRIES)):
        variants = [(f"{_AXIS}={size}", builtin_schedule(name, size))
                    for size in _CONSISTENCY_SIZES]
        findings.extend(check_consistency(variants))
    return findings


# ---------------------------------------------------------------------------
# CLI (tools/hvdsched)
# ---------------------------------------------------------------------------

_SHAPE_RE = re.compile(r"^(?:(\d+(?:x\d+)*))?:?([A-Za-z_]\w*)?$")


def _parse_shape(spec: str):
    """'8x16:float32' / '8x16' / ':bfloat16' -> ShapeDtypeStruct."""
    import jax
    import numpy as np
    m = _SHAPE_RE.match(spec)
    if not m:
        raise ValueError(f"bad --shape spec: {spec!r} "
                         f"(want e.g. 8x16:float32)")
    dims = tuple(int(d) for d in m.group(1).split("x")) if m.group(1) else ()
    dtype = np.dtype(m.group(2) or "float32")
    return jax.ShapeDtypeStruct(dims, dtype)


def _resolve_entry(spec: str):
    """'module:function' -> callable (for user step functions)."""
    import importlib
    mod_name, sep, fn_name = spec.partition(":")
    if not sep:
        raise ValueError(f"--entry {spec!r}: want module:function")
    mod = importlib.import_module(mod_name)
    return getattr(mod, fn_name)


def main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse
    parser = argparse.ArgumentParser(
        prog="tools/hvdsched",
        description="hvdsched: static collective-schedule extractor — "
                    "traces step functions to jaxprs on CPU and "
                    "snapshots/checks the collective schedule "
                    "(docs/analysis.md 'Schedule snapshots')")
    parser.add_argument("--list", action="store_true",
                        help="list builtin entry points")
    parser.add_argument("--emit", metavar="ENTRY",
                        help="print the JSON schedule of a builtin entry")
    parser.add_argument("--check", action="store_true",
                        help="re-trace every builtin entry and diff "
                             "against the committed snapshots (CI mode; "
                             "exit 1 on drift, HVD211)")
    parser.add_argument("--update", action="store_true",
                        help="re-record the snapshots for every builtin "
                             "entry (the explicit, reviewed ratchet step)")
    parser.add_argument("--consistency", action="store_true",
                        help="trace every builtin entry at mesh sizes "
                             f"{list(_CONSISTENCY_SIZES)} and require "
                             "identical canonical schedules (HVD210)")
    parser.add_argument("--dir", metavar="DIR", default=None,
                        help="snapshot directory "
                             "(default: tests/schedules/)")
    parser.add_argument("--entry", metavar="MOD:FN",
                        help="trace a user step function instead of the "
                             "builtins (combine with --shape/--axis)")
    parser.add_argument("--shape", metavar="SPEC", action="append",
                        default=[],
                        help="example argument for --entry, e.g. "
                             "8x16:float32 (repeatable, one per arg)")
    parser.add_argument("--axis", metavar="NAME=SIZE", action="append",
                        default=[],
                        help="axis environment for --entry, e.g. "
                             "workers=2 (repeatable)")
    args = parser.parse_args(argv)

    if args.list:
        print("builtin schedule entries:")
        for name, builder in sorted(BUILTIN_ENTRIES.items()):
            print(f"  {name:18s} {(builder.__doc__ or '').strip().splitlines()[0]}")
        return 0

    if args.entry:
        fn = _resolve_entry(args.entry)
        shapes = [_parse_shape(s) for s in args.shape]
        axis_env = []
        for a in args.axis:
            name, sep, size = a.partition("=")
            if not sep:
                parser.error(f"--axis {a!r}: want NAME=SIZE")
            axis_env.append((name, int(size)))
        sched = trace_schedule(fn, shapes, axis_env=axis_env,
                               entry=args.entry)
        print(sched.to_json(), end="")
        return 0

    if args.emit:
        if args.emit not in BUILTIN_ENTRIES:
            parser.error(f"unknown entry {args.emit!r} (see --list)")
        print(builtin_schedule(args.emit).to_json(), end="")
        return 0

    if args.update:
        directory = args.dir or snapshot_dir()
        os.makedirs(directory, exist_ok=True)
        for name in sorted(BUILTIN_ENTRIES):
            path = snapshot_path(name, directory)
            sched = builtin_schedule(name)
            with open(path, "w", encoding="utf-8") as f:
                f.write(sched.to_json())
            print(f"hvdsched: recorded {path} "
                  f"({len(sched.records)} collective(s))")
        return 0

    if args.check or args.consistency:
        findings: List[Finding] = []
        if args.check:
            findings.extend(check_builtin_snapshots(args.dir))
        if args.consistency:
            findings.extend(check_builtin_consistency())
        for f in findings:
            print(f.format_text())
        if findings:
            print(f"\nhvdsched: {len(findings)} finding(s)")
            return 1
        kinds = [k for k, on in (("snapshots", args.check),
                                 ("consistency", args.consistency)) if on]
        print(f"hvdsched: {len(BUILTIN_ENTRIES)} entr"
              f"{'y' if len(BUILTIN_ENTRIES) == 1 else 'ies'} clean "
              f"({' + '.join(kinds)})")
        return 0

    parser.error("nothing to do (try --check, --update, --emit ENTRY, "
                 "--consistency or --list)")
    return 2


if __name__ == "__main__":
    import sys
    sys.exit(main())

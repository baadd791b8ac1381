"""Mixture-of-Experts layer with expert parallelism (ep mesh axis).

Beyond-reference capability (SURVEY.md §2.9: the reference exposes the
``alltoall`` primitive MoE routing needs but has no MoE layer).  This is
the TPU-native GShard/Switch formulation: top-k routing with a static
capacity (XLA needs static shapes, so overflow tokens drop), dispatch and
combine as one-hot einsums (MXU-friendly), and expert placement over the
``ep`` mesh axis — by default aliased onto ``dp``, the standard layout —
with two tiled ``all_to_all`` exchanges per layer carrying tokens to their
experts and back over ICI.

Two dispatch paths, ``cfg.moe_dispatch``:

* ``"capacity"`` (:func:`moe_layer` below, the default): GShard's static
  capacity and one-hot dispatch; overflow tokens drop; the only path
  with an ``ep`` axis of more than one chip (its two ``all_to_all``).
* ``"dropless"`` (:func:`dropless_moe_layer`): top-k over all
  ``cfg.n_experts`` router outputs in float32 (:func:`route`: a softmax
  over them or a sigmoid of each, with or without a selection bias; a
  shared expert, :func:`shared_expert`, is added by the trunk that has
  one); the layer is told which
  experts it holds (``cfg.experts_first`` and the leading dimension of
  the expert weights it is given: a chip's share of a deployment) and
  computes their part of the result.  The (token, expert) pairs whose
  expert is held are sorted by expert and taken in chunks of a fixed
  number of rows, as many chunks as the routing needs
  (``lax.while_loop``), each a gather of its rows out of their tokens,
  three grouped matrix products and a combine of the weighted rows into
  their tokens.  Gather, products and combine are the Pallas kernels of
  :mod:`horovod_tpu.ops.grouped_matmul` where the backend, the widths and
  the dtype allow (``hvd_moe_dispatch_tokens`` / ``_dout`` write the
  rows the pairs fill, in the products' dtype; ``hvd_moe_gmm_*``
  forward and for the rows' gradients, ``hvd_moe_tgmm_*`` for the
  weights'; the activation, the pairs' weights and the sum of the two
  products behind a row's gradient are applied to the fp32 accumulators,
  and a tile of rows past the pairs costs no product;
  ``hvd_moe_combine_out`` / ``_dtok`` add each float32 row to its token,
  a tile of tokens in VMEM at a time), ``table[tok]``, ``lax.ragged_dot``
  with autodiff's backward and ``.at[].add`` elsewhere (CPU, toy widths):
  one algorithm, no knob; ``hvd_moe_gmm_kernel_total`` says which a
  program took.  No
  capacity, no dropped pair, and device work in proportion to the pairs
  routed here.  What absent experts would add is left out.

Gradient calculus note (see training.py): expert weights are *sharded*
over ep=dp, and the backward all_to_all already sums each expert's
gradient contributions from every data shard, so expert-weight grads need
scaling by 1/(dp·sp) instead of the replicated-param pmean.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import metrics as _metrics
from ..ops import grouped_matmul as _gmm
from ..scopes import SCOPE_EXPERTS, SCOPE_ROUTE, SCOPE_SHARED  # noqa: F401


_m_layers = _metrics.counter(
    "hvd_moe_layer_total",
    "Expert layers built by dispatch path, one per traced call site",
    labels=("path",))
_m_routed = _metrics.counter(
    "hvd_moe_routed_total",
    "What steps' outputs said of dropless expert layers, summed over the "
    "layer-steps recorded (record_routing): pairs routed to held "
    "experts, rows the grouped products computed, the fullest held "
    "expert's pairs, and the layer-steps themselves",
    labels=("what",))
ROUTING_STATS = ("pairs", "rows", "fullest", "layers")


def record_routing(stats) -> None:
    """Add one step's routing statistics (``[4]``, as ROUTING_STATS
    orders them, summed over its layers, fetched from the step's
    output by whoever drives the steps) to ``hvd_moe_routed_total``."""
    if _metrics.ACTIVE:
        for what, value in zip(ROUTING_STATS, np.asarray(stats).tolist()):
            _m_routed.inc(float(value), what=what)


def init_moe_layer_params(key, n_layers, d_model, d_ff, n_experts,
                          param_dtype=jnp.float32, n_held=0):
    """Stacked per-layer MoE params: router + per-expert SwiGLU weights,
    of ``n_held`` experts where the chip holds a share (0 = all)."""
    k = jax.random.split(key, 4)

    def norm(key, shape, fan_in):
        return jax.random.normal(key, shape, param_dtype) * (fan_in ** -0.5)

    L, D, F, E = n_layers, d_model, d_ff, n_experts
    H = n_held or E
    return {
        "router": norm(k[0], (L, D, E), D),
        "we_gate": norm(k[1], (L, H, D, F), D),
        "we_up": norm(k[2], (L, H, D, F), D),
        "we_down": norm(k[3], (L, H, F, D), F),
    }


def _top_k_dispatch(gates, k, capacity):
    """Build dispatch/combine tensors from gate probabilities.

    gates: [N, E] softmax probabilities.  Returns
    (dispatch [N, E, C] one-hot, combine [N, E, C] weighted, aux_loss).
    GShard-style: k sequential top-1 selections, each with its own
    position-in-expert cumsum offset by the previous choices' counts.
    """
    N, E = gates.shape
    remaining = gates
    counts = jnp.zeros((E,), jnp.int32)
    dispatch = jnp.zeros((N, E, capacity), gates.dtype)
    combine = jnp.zeros((N, E, capacity), gates.dtype)
    for _ in range(k):
        idx = jnp.argmax(remaining, axis=-1)                     # [N]
        onehot = jax.nn.one_hot(idx, E, dtype=gates.dtype)       # [N, E]
        pos = (jnp.cumsum(onehot, axis=0) - onehot
               + counts[None, :]) * onehot                        # [N, E]
        keep = (pos < capacity) * onehot
        pos_oh = jax.nn.one_hot(
            pos.sum(-1).astype(jnp.int32), capacity,
            dtype=gates.dtype) * keep.sum(-1, keepdims=True)      # [N, C]
        d = keep[:, :, None] * pos_oh[:, None, :]                 # [N, E, C]
        dispatch = dispatch + d
        combine = combine + d * (gates * onehot).sum(
            -1, keepdims=True)[:, :, None]
        counts = counts + onehot.sum(0).astype(jnp.int32)
        remaining = remaining * (1.0 - onehot)
    # normalize combine weights over the selected experts
    denom = combine.sum(axis=(1, 2), keepdims=True)
    combine = combine / jnp.maximum(denom, 1e-9)
    # load-balance auxiliary loss (Switch Transformer eq. 4)
    frac_tokens = dispatch.sum(axis=(0, 2)) / jnp.maximum(
        dispatch.sum(), 1.0)
    frac_probs = gates.mean(axis=0)
    aux = (frac_tokens * frac_probs).sum() * E
    return dispatch, combine, aux


def moe_layer(x, lp, cfg, par):
    """One MoE sublayer.  x: [B, Tl, D]; lp: this layer's MoE params with
    expert dim already ep-local ([E_local, D, F] …)."""
    if _metrics.ACTIVE:
        _m_layers.inc(path="capacity")
    B, Tl, D = x.shape
    N = B * Tl
    E = cfg.n_experts
    k = cfg.expert_top_k
    ep_ax = par.ep_axis
    ep = lax.axis_size(ep_ax) if ep_ax is not None else 1
    El = lp["we_gate"].shape[0]           # experts held by this shard
    if El * ep != E:
        raise ValueError(f"experts {E} != ep({ep}) * local({El})")
    capacity = int(np.ceil(k * N / E * cfg.capacity_factor))

    tokens = x.reshape(N, D)
    logits = tokens @ lp["router"].astype(x.dtype)                # [N, E]
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    dispatch, combine, aux = _top_k_dispatch(gates, k, capacity)
    dispatch = dispatch.astype(x.dtype)
    combine = combine.astype(x.dtype)

    # dispatch tokens into per-expert slots: [E, C, D]
    slots = jnp.einsum("nec,nd->ecd", dispatch, tokens)
    if ep_ax is not None and ep > 1:
        # experts → their owning shard; each expert gets ep*C slots
        slots = lax.all_to_all(slots, ep_ax, split_axis=0, concat_axis=1,
                               tiled=True)                        # [El, ep*C, D]
    # expert FFN, batched over local experts (one big MXU einsum each)
    gate = jax.nn.silu(jnp.einsum(
        "ecd,edf->ecf", slots, lp["we_gate"].astype(x.dtype)))
    up = jnp.einsum("ecd,edf->ecf", slots, lp["we_up"].astype(x.dtype))
    out = jnp.einsum("ecf,efd->ecd", gate * up,
                     lp["we_down"].astype(x.dtype))
    if ep_ax is not None and ep > 1:
        out = lax.all_to_all(out, ep_ax, split_axis=1, concat_axis=0,
                             tiled=True)                          # [E, C, D]
    # combine expert outputs back to token order
    y = jnp.einsum("ecd,nec->nd", out, combine)
    if par.tp_axis is not None:
        # expert FFNs are also tp-column/row sharded → row reduction
        y = lax.psum(y, par.tp_axis)
    return y.reshape(B, Tl, D), aux.astype(jnp.float32)


# ------------------------------------------------------- dropless path

def _chunk_rows(n_tokens, k, held, n_experts):
    """Rows a chunk takes: half over what even routing sends here, so
    that one chunk is the usual case and a skewed routing takes more
    chunks, not a larger buffer."""
    even = n_tokens * k * held / n_experts
    most = n_tokens * min(k, held)
    return int(min(most, max(512, -(-int(even * 1.5) // 512) * 512)))


def _swiglu(gate, up):
    """``silu(gate) * up`` in float32 of gates and ups rounded to the
    rows' dtype, rounded again: the forward's and the backward's alike."""
    return (jax.nn.silu(gate.astype(jnp.float32))
            * up.astype(jnp.float32)).astype(gate.dtype)


def _gate_up(xs, wg, wu, sizes, keep=False):
    """``(h,)``, or ``(gate, up)`` for the backward: both products from
    one read of a tile of rows."""
    F, dt = wg.shape[2], xs.dtype

    def body(x, a, b):
        gate, up = _gmm.dot(x, a).astype(dt), _gmm.dot(x, b).astype(dt)
        return (gate, up) if keep else (_swiglu(gate, up),)

    return _gmm.gmm(body, (xs,), (wg, wu), sizes,
                    [(F, dt)] * (2 if keep else 1), "gate_up")


def _expert_ffn(xs, wg, wu, wd, wt, sizes):
    """Rows ``xs [R, D]`` sorted by expert, ``sizes [E]`` rows each:
    SwiGLU by each row's expert, weighted ``wt [R]``, float32 out, zero
    on the rows past the sizes' sum."""
    if _gmm.supported(xs, wg, wu, wd):
        h, = _gate_up(xs, wg, wu, sizes)
        ys, = _gmm.gmm(lambda h, w, d: (_gmm.dot(h, d) * w,),
                       (h, wt[:, None]), (wd,), sizes,
                       [(wd.shape[2], jnp.float32)], "down")
        return ys
    _gmm.count_xla(gmm=3)
    h = _swiglu(lax.ragged_dot(xs, wg, sizes), lax.ragged_dot(xs, wu, sizes))
    ys = lax.ragged_dot(h, wd, sizes).astype(jnp.float32) * wt[:, None]
    return jnp.where(_pairs(sizes, ys), ys, 0.0)


def _pairs(sizes, like):
    """``[R, 1]``: which of ``like``'s rows the sizes cover."""
    return (jnp.arange(like.shape[0]) < sizes.sum())[:, None]


def _expert_ffn_grads(xs, wg, wu, wd, wt, sizes, dys, dwg, dwu, dwd):
    """The backward of :func:`_expert_ffn` for the cotangent ``dys [R,
    D]`` (float32, or the rows' dtype already where the kernels run):
    ``(dxs, dwt, dwg, dwu, dwd)``, ``dxs`` float32 and it
    and ``dwt`` zero on the rows past the sizes' sum, the three weights'
    gradients added to the float32 ``dwg``, ``dwu``, ``dwd`` given.  With
    the kernels gate and up are made again and the down product is not:
    a pair's weight meets ``dys @ wd.T`` on the accumulator, where
    ``sum(h * that)`` is the weight's own gradient."""
    if not _gmm.supported(xs, wg, wu, wd):
        _gmm.count_xla(gmm=3, tgmm=3)          # autodiff's six
        _, vjp = jax.vjp(
            lambda xs, a, b, d, w: _expert_ffn(xs, a, b, d, w, sizes),
            xs, wg, wu, wd, wt)
        dxs, da, db, dd, dwt = vjp(dys)
        pairs = _pairs(sizes, dxs)
        return (jnp.where(pairs, dxs.astype(jnp.float32), 0.0),
                jnp.where(pairs[:, 0], dwt, 0.0), dwg + da.astype(jnp.float32),
                dwu + db.astype(jnp.float32), dwd + dd.astype(jnp.float32))
    dt = xs.dtype
    D, F = xs.shape[1], wg.shape[2]
    gate, up = _gate_up(xs, wg, wu, sizes, keep=True)
    dys = dys.astype(dt)

    def dh_body(dy, gate, up, w, d):
        acc = _gmm.dot(dy, d, transposed=True)          # dys @ wd.T
        g, u = gate.astype(jnp.float32), up.astype(jnp.float32)
        sig = jax.nn.sigmoid(g)
        act = g * sig
        h = (act * u).astype(dt).astype(jnp.float32)    # _swiglu's
        dh = acc * w
        return (dh * u * sig * (1.0 + g - act), dh * act, h * w,
                (acc * h).sum(axis=1, keepdims=True))

    dgate, dup, hw, dwt = _gmm.gmm(
        dh_body, (dys, gate, up, wt[:, None]), (wd,), sizes,
        [(F, dt), (F, dt), (F, dt), (1, jnp.float32)], "dh")
    dxs, = _gmm.gmm(
        lambda dg, du, a, b: (_gmm.dot(dg, a, transposed=True)
                              + _gmm.dot(du, b, transposed=True),),
        (dgate, dup), (wg, wu), sizes, [(D, jnp.float32)], "dx")
    return (dxs, dwt[:, 0], _gmm.tgmm(xs, dgate, sizes, dwg, "gate"),
            _gmm.tgmm(xs, dup, sizes, dwu, "up"),
            _gmm.tgmm(hw, dys, sizes, dwd, "down"))


def _kernels(rows, width, ws) -> bool:
    """Whether a chunk of ``rows`` rows of ``width`` is the kernels': the
    grouped products' and, before them, the dispatch's."""
    return _gmm.supported(
        jax.ShapeDtypeStruct((rows, width), ws[0].dtype), *ws)


def _chunk_of(table, tok, sizes, ws, name):
    """The chunk's rows, ``table[tok]``: where the grouped products are
    the kernels', by the dispatch kernel (``hvd_moe_dispatch_<name>``), in
    the weights' dtype and only the rows the sizes cover (the products
    and the combine read no other into a result); elsewhere XLA's gather
    of every row, in the table's dtype."""
    if _kernels(tok.shape[0], table.shape[1], ws):
        return _gmm.dispatch(table, tok, sizes, ws[0].dtype, name)
    _gmm.count_xla(gather=1)
    return table[tok]


def _chunks(order, pair_w, sizes, k, rows):
    """``(n, chunk)``: how many chunks of ``rows`` sorted pairs the
    held pairs fill, and ``chunk(c)`` = (token of each row, its weight,
    rows per expert, first sorted position)."""
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    n = (ends[-1] + rows - 1) // rows

    def chunk(c):
        lo = c * rows
        pairs = lax.dynamic_slice(order, (lo,), (rows,))
        here = jnp.clip(jnp.minimum(ends, lo + rows)
                        - jnp.maximum(starts, lo), 0, rows)
        return pairs // k, pair_w[pairs], here.astype(jnp.int32), lo

    return n, chunk


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _held_experts(tokens, wg, wu, wd, pair_w, order, sizes, k, rows):
    """``([N, D] float32, rows)``: for every token the weighted outputs
    of its held experts, and how many rows the grouped products were
    given.  ``order``: pair ids ``token * k + j`` sorted by held expert
    and, inside an expert, by pair id (pairs of experts not held last),
    padded by ``rows``; a token's ``k`` experts are distinct, so its
    tokens ascend inside an expert's rows, which the combine counts on;
    ``sizes [E_held]``; ``pair_w [N * k]`` float32."""
    n, chunk = _chunks(order, pair_w, sizes, k, rows)

    def body(c, carry):
        out, computed = carry
        tok, wt, here, _ = chunk(c)
        xs = _chunk_of(tokens, tok, here, (wg, wu, wd), "tokens")
        ys = _expert_ffn(xs, wg, wu, wd, wt, here)
        return (_gmm.combine(ys, tok, here, out, "out", fresh=c == 0),
                computed + here.sum())

    return lax.fori_loop(0, n, body, ((tokens * 0).astype(jnp.float32),
                                      sizes[0] * 0))


def _held_experts_fwd(tokens, wg, wu, wd, pair_w, order, sizes, k, rows):
    out = _held_experts(tokens, wg, wu, wd, pair_w, order, sizes, k, rows)
    return out, (tokens, wg, wu, wd, pair_w, order, sizes)


def _held_experts_bwd(k, rows, res, cotangents):
    """The same walk over the chunks; each chunk's products are made
    again from the rows and differentiated there, so nothing of size
    rows x width is kept from the forward pass."""
    dout = cotangents[0]
    tokens, wg, wu, wd, pair_w, order, sizes = res
    ws = (wg, wu, wd)
    if _kernels(rows, tokens.shape[1], ws):
        # rounded to the rows' dtype once a token, not once a pair behind
        # the gather (XLA turns ``dout[tok].astype`` around the same way)
        dout = dout.astype(wg.dtype)
    n, chunk = _chunks(order, pair_w, sizes, k, rows)
    f32 = lambda a: (a * 0).astype(jnp.float32)

    def body(c, carry):
        dtok, dwg, dwu, dwd, dwt_sorted = carry
        tok, wt, here, lo = chunk(c)
        dxs, dwt, dwg, dwu, dwd = _expert_ffn_grads(
            _chunk_of(tokens, tok, here, ws, "tokens"), wg, wu, wd, wt, here,
            _chunk_of(dout, tok, here, ws, "dout"), dwg, dwu, dwd)
        return (_gmm.combine(dxs, tok, here, dtok, "dtok", fresh=c == 0),
                dwg, dwu, dwd,
                lax.dynamic_update_slice(dwt_sorted, dwt, (lo,)))

    dtok, dwg, dwu, dwd, dwt_sorted = lax.fori_loop(
        0, n, body, (f32(tokens), f32(wg), f32(wu), f32(wd),
                     jnp.zeros(order.shape, jnp.float32) + f32(pair_w[:1])))
    # weights' cotangents back from sorted order to pair order
    # (a sort by pair id: a gather of as many scalars costs ten times it)
    n_pairs = pair_w.shape[0]
    _, dpair_w = lax.sort_key_val(order[:n_pairs], dwt_sorted[:n_pairs])
    return (dtok.astype(tokens.dtype), dwg.astype(wg.dtype),
            dwu.astype(wu.dtype), dwd.astype(wd.dtype), dpair_w, None, None)


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


SCORES = ("softmax", "sigmoid")


def route(tokens, router, k, score="softmax", bias=None, scaling=1.0,
          eps=0.0):
    """``(expert ids [N, k], weights [N, k] float32)``: every one of the
    router's outputs scored in float32, by ``score``: ``"softmax"`` over
    all of them, or ``"sigmoid"`` of each alone (DeepSeek-V3's); the ``k``
    with the largest score are chosen, or, given a selection ``bias [E]``,
    the largest ``score + bias``; the weights are the chosen experts'
    scores (without the bias: it moves the choice and nothing else),
    renormalised over those ``k`` whether their experts are held here or
    not (their sum plus ``eps``; at 0.0 nothing is added), times
    ``scaling`` (DeepSeek-V3's ``routed_scaling_factor``; at 1.0 nothing
    is multiplied).  The bias gets no gradient."""
    if score not in SCORES:
        raise ValueError(f"score must be one of {SCORES}, got {score!r}")
    logits = jnp.dot(tokens.astype(jnp.float32), router.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    scores = (jax.nn.softmax(logits, axis=-1) if score == "softmax"
              else jax.nn.sigmoid(logits))
    if bias is None:
        top_p, top_i = lax.top_k(scores, k)
    else:
        _, top_i = lax.top_k(scores + bias.astype(jnp.float32), k)
        top_p = jnp.take_along_axis(scores, top_i, axis=-1)
    total = top_p.sum(axis=-1, keepdims=True)
    top_w = top_p / (total + eps if eps else total)
    return top_i, top_w if scaling == 1.0 else top_w * scaling


def shared_expert(x, w_gate_up, w_down):
    """The expert every token passes through, ``W_down (silu(g) * u)`` with
    ``[g ; u] = W_gate_up x``: gate and up as one product, no routing, no
    grouped kernel.  It is added to the routed experts' result once, on
    every chip alike (replicated; never over an ``ep`` axis)."""
    gate, up = jnp.split(x @ w_gate_up.astype(x.dtype), 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ w_down.astype(x.dtype)


def dropless_moe_layer(x, lp, cfg, par):
    """One routed expert sublayer that drops no token.  ``x [B, T, D]``;
    ``lp["router"] [D, cfg.n_experts]``, scored by ``cfg.router_score``
    and chosen with ``lp["router_bias"] [cfg.n_experts]`` where the layer
    has one; ``lp["we_*"]`` the experts held here, ``cfg.experts_first``
    the id of the first.  Returns the held experts' part of the layer's
    output and ``[4]`` float32 statistics (ROUTING_STATS: pairs routed
    here, rows computed, the fullest held expert's pairs, 1).  A shared
    expert is the caller's to add (:func:`shared_expert`)."""
    if par.tp_axis is not None or par.ep_axis is not None:
        raise NotImplementedError(
            "dropless experts run on the experts a chip holds, and a shared "
            "expert beside them whole on every chip; over a tp or ep axis "
            "the layer is the capacity path's (moe_dispatch='capacity'), "
            "which has no shared expert")
    if _metrics.ACTIVE:
        _m_layers.inc(path="dropless")
    B, T, D = x.shape
    N, k = B * T, cfg.expert_top_k
    held = lp["we_gate"].shape[0]
    tokens = x.reshape(N, D)
    with jax.named_scope(SCOPE_ROUTE):
        top_i, top_w = route(tokens, lp["router"], k, cfg.router_score,
                             lp.get("router_bias"),
                             cfg.routed_scaling_factor, cfg.router_eps)
    with jax.named_scope(SCOPE_EXPERTS):
        local = (top_i - cfg.experts_first).reshape(-1)
        e = jnp.where((local >= 0) & (local < held), local, held)
        rows = _chunk_rows(N, k, held, cfg.n_experts)
        # held pairs first, by expert; the rest after them, never read
        order = jnp.pad(jnp.argsort(e, stable=True).astype(jnp.int32),
                        (0, rows))
        sizes = (e[:, None] == jnp.arange(held)[None]).sum(0).astype(jnp.int32)
        ws = [lp[n].astype(x.dtype) for n in ("we_gate", "we_up", "we_down")]
        y, computed = _held_experts(tokens, *ws, top_w.reshape(-1), order,
                                    sizes, k, rows)
        stats = jnp.stack([sizes.sum(), computed, sizes.max(),
                           sizes[0] * 0 + 1]).astype(jnp.float32)
    return y.astype(x.dtype).reshape(B, T, D), stats

"""Fused flash-attention Pallas kernels for TPU.

The hot op of the flagship model (SURVEY.md §6: the rebuild's headline
benchmark is transformer training throughput).  The reference keeps its
hot loops in hand-written CUDA (`horovod/common/ops/cuda/cuda_kernels.cu`
per SURVEY §2.1); the TPU-native equivalent is a Pallas kernel: the
online-softmax recurrence runs in VMEM so the ``[T, T]`` score matrix
never touches HBM, q/k tiles feed the MXU directly, and the backward
pass recomputes score tiles from the saved logsumexp instead of storing
them.

Public layout contract (matches :mod:`horovod_tpu.parallel.ring_attention`):
  q: ``[B, T, H, D]``   k/v: ``[B, Tk, Hkv, D]`` with ``Hkv | H`` (GQA —
  query head h reads kv head ``h // (H//Hkv)``; the kernels run in
  ``[B, H, T, D]`` layout internally for TPU tiling).

The logsumexp residual is stored blocked as ``[B, H, nq, bq]`` — the
(nq, bq) trailing dims are full blocks, which satisfies Mosaic's tiling
rule without the 128-lane padding the naive ``[B, H, T]`` layout needs.

Falls back cleanly: :func:`supported` gates on platform/shape so callers
(e.g. ``local_attention``) can pick the XLA blockwise path on CPU meshes
or odd shapes — on a TPU backend each refused shape is logged once, at
WARNING, with the test that refused it.  ``HOROVOD_FLASH_ATTENTION=0``
disables the kernel.
"""

from __future__ import annotations

import functools
import logging
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

logger = logging.getLogger("horovod_tpu")

NEG_INF = -1e30
_INTERPRET = False  # flipped by tests to run kernels on CPU
_VMEM_BUDGET = 10 * 1024 * 1024  # soft cap for resident kernel buffers


def _block_sizes(t_q: int, t_kv: int):
    """Query/key block sizes for the kernel grid.

    ``HOROVOD_FLASH_BLOCK`` overrides the 512 default (the measured
    best on v5e at the flagship geometry; tools/flash_sweep.py measures
    candidates — the reference tuned its fusion analogs through the
    autotuner the same way).  The override is clamped to the sequence
    lengths; supported() still rejects non-dividing or non-128-multiple
    results, falling back to the XLA attention path."""
    try:
        blk = int(os.environ.get("HOROVOD_FLASH_BLOCK", "512") or 512)
    except ValueError:
        blk = 512
    if blk <= 0:  # 0/negative would crash the divisibility gate; use
        blk = 512  # HOROVOD_FLASH_ATTENTION=0 to disable the kernel
    bq = min(blk, t_q)
    bk = min(blk, t_kv)
    return bq, bk


def _sds(shape, dtype, *operands):
    """ShapeDtypeStruct carrying the union of the operands' varying mesh
    axes — required for pallas_call outputs under shard_map check_vma."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


@functools.lru_cache(maxsize=None)
def _warn_refused(kernel: str, shapes: tuple, reason: str) -> None:
    logger.warning("%s kernel refused shapes %s (%s); falling back to "
                   "the XLA path", kernel, shapes, reason)


def _verdict(kernel: str, reason: Optional[str], *operands) -> bool:
    """``reason is None``, said aloud where it matters: on a TPU the XLA
    path is a slower program than the one the caller named, so each
    refused (kernel, shapes, reason) is logged once, at WARNING."""
    if reason is not None and jax.default_backend() == "tpu":
        _warn_refused(kernel, tuple(tuple(x.shape) for x in operands),
                      reason)
    return reason is None


def _refusal(q, k, v) -> Optional[str]:
    """Which test keeps the Pallas kernel off this call; None = it runs."""
    if os.environ.get("HOROVOD_FLASH_ATTENTION", "1") in ("0", "false"):
        return "HOROVOD_FLASH_ATTENTION is off"
    if not _INTERPRET and jax.default_backend() != "tpu":
        return f"backend is {jax.default_backend()}, not tpu"
    if q.ndim != 4 or k.ndim != 4:
        return "q and k must be rank 4"
    B, T, H, D = q.shape
    Tk, Hkv = k.shape[1], k.shape[2]
    if v.shape != k.shape or q.shape[0] != k.shape[0] or k.shape[3] != D:
        return "v must match k, and k must match q in batch and head_dim"
    if H % Hkv:
        return f"kv heads {Hkv} do not divide query heads {H}"
    if D % 64 or D > 256:
        return f"head_dim {D} is not a multiple of 64 up to 256"
    bq, bk = _block_sizes(T, Tk)
    if T % bq or Tk % bk or bq % 128 or bk % 128:
        return (f"blocks ({bq}, {bk}) must divide the sequence lengths "
                f"({T}, {Tk}) and be multiples of 128")
    if q.dtype not in (jnp.bfloat16, jnp.float32):
        return f"dtype {q.dtype} is neither bfloat16 nor float32"
    g = H // Hkv
    # fwd holds k+v [Tk, D]; bwd dkv holds q+do [g*T, D] per group
    resident = max(2 * Tk * D, 2 * g * T * D) * q.dtype.itemsize
    if resident > _VMEM_BUDGET:
        return (f"resident buffers need {resident} bytes of VMEM, over "
                f"the {_VMEM_BUDGET} budget")
    return None


def supported(q, k, v, causal: bool = True) -> bool:
    """True when the Pallas kernel can run this shape on this backend."""
    return _verdict("flash_attention", _refusal(q, k, v), q, k, v)


# ---------------------------------------------------------------- forward

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                bk, nkv):
    bq, D = q_ref.shape[2], q_ref.shape[3]
    i = pl.program_id(2)
    q = q_ref[0, 0]

    if causal:
        hi = jnp.minimum(lax.div((i + 1) * bq + bk - 1, bk), nkv)
    else:
        hi = nkv

    def body(j, carry):
        m, l, acc = carry
        kj = k_ref[0, 0, pl.ds(j * bk, bk), :]
        vj = v_ref[0, 0, pl.ds(j * bk, bk), :]
        s = lax.dot_general(q, kj, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        if causal:
            rows = lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + i * bq
            cols = lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + j * bk
            s = jnp.where(cols <= rows, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1, keepdims=True)
        pv = jnp.dot(p.astype(vj.dtype), vj,
                     preferred_element_type=jnp.float32)
        return m_new, l_new, acc * corr + pv

    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    a0 = jnp.zeros((bq, D), jnp.float32)
    m, l, acc = lax.fori_loop(0, hi, body, (m0, l0, a0))
    o_ref[0, 0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0, 0, i, :] = (m + jnp.log(l)).reshape(bq)


def _flash_fwd_bhtd(q, k, v, causal, scale):
    """q [B,H,T,D], k/v [B,Hkv,Tk,D] → (out [B,H,T,D], lse [B,H,nq,bq])."""
    B, H, T, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    g = H // Hkv
    bq, bk = _block_sizes(T, Tk)
    nq, nkv = T // bq, Tk // bk

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               bk=bk, nkv=nkv)
    out, lse = pl.pallas_call(
        kernel,
        grid=(B, H, nq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, Tk, D), lambda b, h, i: (b, h // g, 0, 0)),
            pl.BlockSpec((1, 1, Tk, D), lambda b, h, i: (b, h // g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i: (b, h, i, 0)),
            # lse block is per-(b,h): consecutive i steps reuse the same
            # VMEM buffer, each filling its own row, flushed on (b,h) change
            pl.BlockSpec((1, 1, nq, bq), lambda b, h, i: (b, h, 0, 0)),
        ],
        out_shape=[
            _sds((B, H, T, D), q.dtype, q, k, v),
            _sds((B, H, nq, bq), jnp.float32, q, k, v),
        ],
        interpret=_INTERPRET,
        name="hvd_flash_fwd",
    )(q, k, v)
    return out, lse


# --------------------------------------------------------------- backward

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
               scale, causal, bk, nkv):
    bq, D = q_ref.shape[2], q_ref.shape[3]
    i = pl.program_id(2)
    q = q_ref[0, 0]
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0, i, :].reshape(bq, 1)
    delta = delta_ref[0, 0, i, :].reshape(bq, 1)

    if causal:
        hi = jnp.minimum(lax.div((i + 1) * bq + bk - 1, bk), nkv)
    else:
        hi = nkv

    def body(j, dq_acc):
        kj = k_ref[0, 0, pl.ds(j * bk, bk), :]
        vj = v_ref[0, 0, pl.ds(j * bk, bk), :]
        s = lax.dot_general(q, kj, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        if causal:
            rows = lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + i * bq
            cols = lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + j * bk
            s = jnp.where(cols <= rows, s, NEG_INF)
        p = jnp.exp(s - lse)                      # [bq, bk]
        dp = lax.dot_general(do, vj.astype(jnp.float32),
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        return dq_acc + jnp.dot(ds.astype(kj.dtype), kj,
                                preferred_element_type=jnp.float32)

    dq = lax.fori_loop(0, hi, body, jnp.zeros((bq, D), jnp.float32))
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, *, scale, causal, bq, nq, g):
    bk, D = k_ref.shape[2], k_ref.shape[3]
    j = pl.program_id(2)
    kb = k_ref[0, 0]
    vb = v_ref[0, 0]

    lo = lax.div(j * bk, bq) if causal else 0

    dk_acc = jnp.zeros((bk, D), jnp.float32)
    dv_acc = jnp.zeros((bk, D), jnp.float32)
    for hq in range(g):  # static unroll over the GQA group
        def body(i, carry):
            dk_acc, dv_acc = carry
            qi = q_ref[0, hq, pl.ds(i * bq, bq), :]
            doi = do_ref[0, hq, pl.ds(i * bq, bq), :].astype(jnp.float32)
            lse = lse_ref[0, hq, i, :].reshape(bq, 1)
            delta = delta_ref[0, hq, i, :].reshape(bq, 1)
            s = lax.dot_general(qi, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            if causal:
                rows = (lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
                        + i * bq)
                cols = (lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
                        + j * bk)
                s = jnp.where(cols <= rows, s, NEG_INF)
            p = jnp.exp(s - lse)                  # [bq, bk]
            dv_new = dv_acc + lax.dot_general(
                p.astype(doi.dtype), doi, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = lax.dot_general(doi, vb.astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            ds = p * (dp - delta) * scale
            dk_new = dk_acc + lax.dot_general(
                ds, qi.astype(jnp.float32), (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return dk_new, dv_new

        dk_acc, dv_acc = lax.fori_loop(lo, nq, body, (dk_acc, dv_acc))
    dk_ref[0, 0] = dk_acc.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv_acc.astype(dv_ref.dtype)


def _flash_bwd_bhtd(q, k, v, out, lse, do, causal, scale, dlse=None):
    B, H, T, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    g = H // Hkv
    bq, bk = _block_sizes(T, Tk)
    nq, nkv = T // bq, Tk // bk

    # delta_i = rowsum(dO * O) — cheap elementwise, stays in XLA.
    # When the caller differentiates through the exposed lse (ring-step
    # merging), its cotangent folds in exactly here: dlse/ds = p, so
    # ds = p·(dp − delta) + p·dlse = p·(dp − (delta − dlse)).
    delta = jnp.einsum("bhtd,bhtd->bht", do.astype(jnp.float32),
                       out.astype(jnp.float32)).reshape(B, H, nq, bq)
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal, bk=bk,
                          nkv=nkv),
        grid=(B, H, nq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, Tk, D), lambda b, h, i: (b, h // g, 0, 0)),
            pl.BlockSpec((1, 1, Tk, D), lambda b, h, i: (b, h // g, 0, 0)),
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, nq, bq), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, nq, bq), lambda b, h, i: (b, h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i: (b, h, i, 0)),
        out_shape=_sds((B, H, T, D), q.dtype, q, k, v, do),
        interpret=_INTERPRET,
        name="hvd_flash_dq",
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal, bq=bq,
                          nq=nq, g=g),
        grid=(B, Hkv, nkv),
        in_specs=[
            pl.BlockSpec((1, g, T, D), lambda b, c, j: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, c, j: (b, c, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, c, j: (b, c, j, 0)),
            pl.BlockSpec((1, g, T, D), lambda b, c, j: (b, c, 0, 0)),
            pl.BlockSpec((1, g, nq, bq), lambda b, c, j: (b, c, 0, 0)),
            pl.BlockSpec((1, g, nq, bq), lambda b, c, j: (b, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, D), lambda b, c, j: (b, c, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, c, j: (b, c, j, 0)),
        ],
        out_shape=[
            _sds((B, Hkv, Tk, D), k.dtype, q, k, v, do),
            _sds((B, Hkv, Tk, D), v.dtype, q, k, v, do),
        ],
        interpret=_INTERPRET,
        name="hvd_flash_dkv",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ------------------------------------------------------------- public op
# The GQA group reshape in _dkv_kernel's q block assumes query heads of
# one kv group are contiguous (head h ↔ kv head h // g), matching
# jnp.repeat(k, g, axis=head) semantics used across the framework.
# One custom_vjp serves both entry points: the plain path is the lse path
# with a zero lse cotangent (folded into delta as a cheap subtract).

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_attention_lse(q, k, v, causal, scale):
    return _flash_fwd_bhtd(q, k, v, causal, scale)


def _flash_attention_lse_fwd(q, k, v, causal, scale):
    out, lse = _flash_fwd_bhtd(q, k, v, causal, scale)
    return (out, lse), (q, k, v, out, lse)


def _flash_attention_lse_bwd(causal, scale, res, cotangents):
    do, dlse = cotangents
    q, k, v, out, lse = res
    return _flash_bwd_bhtd(q, k, v, out, lse, do, causal, scale,
                           dlse=dlse)


_flash_attention_lse.defvjp(_flash_attention_lse_fwd,
                            _flash_attention_lse_bwd)


def flash_attention(q, k, v, causal: bool = True,
                    sm_scale: Optional[float] = None):
    """Fused exact attention.  ``q [B,T,H,D]``, ``k/v [B,Tk,Hkv,D]``."""
    scale = float(sm_scale if sm_scale is not None
                  else q.shape[-1] ** -0.5)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out, _ = _flash_attention_lse(qt, kt, vt, bool(causal), scale)
    return out.transpose(0, 2, 1, 3)


def flash_attention_lse(q, k, v, causal: bool = True,
                        sm_scale: Optional[float] = None):
    """Fused attention returning ``(out, lse)`` for tile merging.

    ``out [B,T,H,D]``, ``lse [B,H,T]`` (logsumexp of the masked scores per
    query row).  The ring-attention path merges per-step tiles computed by
    this kernel into its online-softmax accumulator; gradients flow
    through both outputs (the lse cotangent folds into the backward
    kernels' delta term).
    """
    scale = float(sm_scale if sm_scale is not None
                  else q.shape[-1] ** -0.5)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out, lse = _flash_attention_lse(qt, kt, vt, bool(causal), scale)
    B, H, T, _ = qt.shape
    return out.transpose(0, 2, 1, 3), lse.reshape(B, H, T)

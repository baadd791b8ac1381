"""Fused softmax-cross-entropy Pallas kernels for the vocabulary head.

The Llama loss ``mean(logsumexp(h @ W.T) - logit[target])`` is the
single biggest non-attention op in the flagship step: at B8·T1024·V32k
the logits tile is 1 GB fp32 before log-softmax doubles it.  The
chunked-scan form (`models/llama.py _chunked_xent`) removes the
materialization in XLA; these kernels go further and fuse the head
matmul with the online-softmax reduction so logits never exist beyond a
``[br, bv]`` VMEM tile — the flash-attention treatment applied to the
vocabulary dimension.

Kernel shapes: ``h [N, D]`` (N = B·T flattened tokens), ``W [V, D]``
(the tied embedding, fp32 master — cast to compute dtype in-register),
``targets [N]``.  The vocab axis is a grid dimension; per-row-block
outputs (m, l, target-logit) accumulate across revisited output blocks
— TPU Pallas executes the grid sequentially, so the innermost vocab
steps form an online-softmax recurrence exactly like flash attention's
kv loop.  Per-token vectors are laid out blocked ``[nr, 1, br]`` (full
blocks, no 128-lane padding — the same trick as the flash kernel's
blocked lse; the singleton middle axis makes each ``(1, 1, br)`` block's
trailing dims equal the array's, which Mosaic's block-shape rule
requires when the sublane dim is not a multiple of 8).

Backward recomputes score tiles from the saved logsumexp: ``dh`` loops
vocab blocks per row block, ``dW`` loops row blocks per vocab block;
``p - onehot`` is formed in-register via an iota match, never stored.
Both accumulate fp32; the scalar upstream cotangent is applied outside
the kernels (a traced value cannot be a static kernel parameter).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _sds, _verdict

_VMEM_CAP = 100 * 1024 * 1024  # leave headroom below the 128MB VMEM


def _vmem_budget(br: int, bv: int, d: int) -> int:
    """Upper bound on the kernels' scoped-VMEM working set in bytes.

    The dW kernel dominates: fp32 ``[bv, D]`` embedding and cotangent
    blocks, double-buffered, plus the ``[br, D]`` activation block and
    the ``[br, bv]`` score/softmax tiles — ~22MB at bv=512, D=2048
    (matches the Mosaic allocator's report) and linear in D."""
    return (4 * bv * d * 4        # w + dw blocks, double-buffered, fp32
            + 2 * br * d * 4      # h block (compute dtype <= fp32)
            + 4 * br * bv * 4     # s/p tiles and their temporaries
            + 8 * 1024 * 1024)    # margin for Mosaic's own scratch


def _compiler_params(br: int, bv: int, d: int):
    """Mosaic's default 16MB scoped-vmem budget rejects the dW kernel's
    working set; grant what the shapes need (capped below VMEM size —
    supported() rejects shapes over the cap).  Interpret mode (CPU
    tests) takes no compiler params."""
    if _INTERPRET:
        return None
    grant = max(32 * 1024 * 1024, min(_vmem_budget(br, bv, d), _VMEM_CAP))
    return pltpu.CompilerParams(vmem_limit_bytes=grant)

NEG_INF = -1e30
_INTERPRET = False  # flipped by tests to run kernels on CPU


def _extra_vma(x, like):
    """Mesh axes ``like`` varies over that ``x`` does not."""
    return tuple(sorted(jax.typeof(like).vma - jax.typeof(x).vma))


def _match_vma(x, like):
    """pvary ``x`` up to ``like``'s varying mesh axes: ops inside the
    kernel require operands with matching vma sets, and the replicated
    embedding must join the activations' axes (free — pvary is a
    type-level cast for replicated values)."""
    extra = _extra_vma(x, like)
    if not extra:
        return x
    return lax.pcast(x, extra, to="varying")


def _blocks(n_rows: int, vocab: int):
    br = next((b for b in (256, 128, 64, 32, 16, 8) if n_rows % b == 0),
              None)
    bv = next((b for b in (512, 256, 128) if vocab % b == 0), None)
    return br, bv


def _refusal(h, w, targets):
    """Which test keeps the fused kernel off this call; None = it runs."""
    if os.environ.get("HOROVOD_FUSED_XENT", "1") in ("0", "false"):
        return "HOROVOD_FUSED_XENT is off"
    if not _INTERPRET and jax.default_backend() != "tpu":
        return f"backend is {jax.default_backend()}, not tpu"
    if h.ndim != 3 or w.ndim != 2 or targets.ndim != 2:
        return "h, w, targets must be rank 3, 2, 2"
    N = h.shape[0] * h.shape[1]
    D = h.shape[2]
    V = w.shape[0]
    if w.shape[1] != D or targets.shape[:2] != h.shape[:2]:
        return "w and targets do not match h"
    if D % 128:
        return f"d_model {D} is not a multiple of 128"
    br, bv = _blocks(N, V)
    if br is None or bv is None:
        return (f"no row block divides {N} tokens or no vocab block "
                f"divides {V}")
    # shapes whose kernel working set cannot fit VMEM (large D: the
    # budget passes 100MB between D=8192 and D=16384) must take the
    # chunked-XLA loss instead of failing Mosaic compilation
    need = _vmem_budget(br, bv, D)
    if need > _VMEM_CAP:
        return (f"working set needs {need} bytes of VMEM, over the "
                f"{_VMEM_CAP} cap")
    return None


def supported(h, w, targets) -> bool:
    """True when the fused kernel can run this shape on this backend."""
    return _verdict("fused_xent", _refusal(h, w, targets), h, w, targets)


# ---------------------------------------------------------------- forward

def _fwd_kernel(h_ref, w_ref, y_ref, m_ref, l_ref, tgt_ref, *, bv):
    j = pl.program_id(1)
    h = h_ref[...]                                   # [br, D]
    wj = w_ref[...].astype(h.dtype)                  # [bv, D]
    br = h.shape[0]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        tgt_ref[...] = jnp.zeros_like(tgt_ref)

    s = lax.dot_general(h, wj, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)  # [br, bv]
    m = m_ref[0, 0]                                  # [br]
    l = l_ref[0, 0]
    m_new = jnp.maximum(m, s.max(axis=-1))
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.exp(s - m_new[:, None]).sum(axis=-1)
    m_ref[0, 0] = m_new
    l_ref[0, 0] = l_new

    # target logit: rows whose label falls inside this vocab block
    local = y_ref[0, 0] - j * bv                     # [br]
    cols = lax.broadcasted_iota(jnp.int32, (br, bv), 1)
    hit = cols == local[:, None]
    tgt_ref[0, 0] = tgt_ref[0, 0] + jnp.where(hit, s, 0.0).sum(axis=-1)


def _xent_fwd(h, w, y_blocked, br, bv):
    N, D = h.shape
    V = w.shape[0]
    nr, nv = N // br, V // bv
    w = _match_vma(w, h)
    m, l, tgt = pl.pallas_call(
        functools.partial(_fwd_kernel, bv=bv),
        grid=(nr, nv),
        in_specs=[
            pl.BlockSpec((br, D), lambda r, j: (r, 0)),
            pl.BlockSpec((bv, D), lambda r, j: (j, 0)),
            pl.BlockSpec((1, 1, br), lambda r, j: (r, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, br), lambda r, j: (r, 0, 0)),
            pl.BlockSpec((1, 1, br), lambda r, j: (r, 0, 0)),
            pl.BlockSpec((1, 1, br), lambda r, j: (r, 0, 0)),
        ],
        out_shape=[
            _sds((nr, 1, br), jnp.float32, h, w),
            _sds((nr, 1, br), jnp.float32, h, w),
            _sds((nr, 1, br), jnp.float32, h, w),
        ],
        interpret=_INTERPRET,
        compiler_params=_compiler_params(br, bv, D),
    )(h, w, y_blocked)
    lse = m + jnp.log(l)                             # [nr, 1, br]
    return lse, tgt


# --------------------------------------------------------------- backward

def _dh_kernel(h_ref, w_ref, y_ref, lse_ref, dh_ref, *, bv):
    j = pl.program_id(1)
    h = h_ref[...]
    wj = w_ref[...].astype(h.dtype)
    br = h.shape[0]

    @pl.when(j == 0)
    def _init():
        dh_ref[...] = jnp.zeros_like(dh_ref)

    s = lax.dot_general(h, wj, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
    p = jnp.exp(s - lse_ref[0, 0][:, None])          # softmax tile
    local = y_ref[0, 0] - j * bv
    cols = lax.broadcasted_iota(jnp.int32, (br, bv), 1)
    p = jnp.where(cols == local[:, None], p - 1.0, p)
    dh_ref[...] = dh_ref[...] + lax.dot_general(
        p.astype(wj.dtype), wj, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _dw_kernel(h_ref, w_ref, y_ref, lse_ref, dw_ref, *, bv):
    j = pl.program_id(0)
    r = pl.program_id(1)
    h = h_ref[...]
    wj = w_ref[...].astype(h.dtype)
    br = h.shape[0]

    @pl.when(r == 0)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    s = lax.dot_general(h, wj, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
    p = jnp.exp(s - lse_ref[0, 0][:, None])
    local = y_ref[0, 0] - j * bv
    cols = lax.broadcasted_iota(jnp.int32, (br, bv), 1)
    p = jnp.where(cols == local[:, None], p - 1.0, p)
    dw_ref[...] = dw_ref[...] + lax.dot_general(
        p.astype(h.dtype), h, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _xent_bwd_kernels(h, w, y_blocked, lse, br, bv):
    N, D = h.shape
    V = w.shape[0]
    nr, nv = N // br, V // bv
    w = _match_vma(w, h)

    dh32 = pl.pallas_call(
        functools.partial(_dh_kernel, bv=bv),
        grid=(nr, nv),
        in_specs=[
            pl.BlockSpec((br, D), lambda r, j: (r, 0)),
            pl.BlockSpec((bv, D), lambda r, j: (j, 0)),
            pl.BlockSpec((1, 1, br), lambda r, j: (r, 0, 0)),
            pl.BlockSpec((1, 1, br), lambda r, j: (r, 0, 0)),
        ],
        out_specs=pl.BlockSpec((br, D), lambda r, j: (r, 0)),
        out_shape=_sds((N, D), jnp.float32, h, w),
        interpret=_INTERPRET,
        compiler_params=_compiler_params(br, bv, D),
    )(h, w, y_blocked, lse)

    dw32 = pl.pallas_call(
        functools.partial(_dw_kernel, bv=bv),
        grid=(nv, nr),
        in_specs=[
            pl.BlockSpec((br, D), lambda j, r: (r, 0)),
            pl.BlockSpec((bv, D), lambda j, r: (j, 0)),
            pl.BlockSpec((1, 1, br), lambda j, r: (r, 0, 0)),
            pl.BlockSpec((1, 1, br), lambda j, r: (r, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bv, D), lambda j, r: (j, 0)),
        out_shape=_sds((V, D), jnp.float32, h, w),
        interpret=_INTERPRET,
        compiler_params=_compiler_params(br, bv, D),
    )(h, w, y_blocked, lse)
    return dh32, dw32


# ------------------------------------------------------------- public op

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _xent_sum(h, w, y_blocked, br, bv):
    lse, tgt = _xent_fwd(h, w, y_blocked, br, bv)
    return (lse - tgt).sum()


def _xent_sum_fwd(h, w, y_blocked, br, bv):
    lse, tgt = _xent_fwd(h, w, y_blocked, br, bv)
    return (lse - tgt).sum(), (h, w, y_blocked, lse)


def _xent_sum_bwd(br, bv, res, g):
    import numpy as np
    h, w, y_blocked, lse = res
    dh32, dw32 = _xent_bwd_kernels(h, w, y_blocked, lse, br, bv)
    # the scalar cotangent applies outside the kernels (traced values
    # cannot parameterize a kernel statically); integer targets get the
    # float0 zero cotangent jax requires for int primals
    dy = np.zeros(y_blocked.shape, jax.dtypes.float0)
    dw = dw32 * g
    # Inside shard_map the embedding is replicated over the data axes
    # while h (and the upstream cotangent g) vary over them: the dW
    # cotangent must carry the cross-shard psum itself — a custom_vjp IS
    # the transpose rule, so check_vma cannot insert it for us.  psum
    # AFTER scaling by g: Σ_shards g·dW_shard is the total gradient, and
    # scaling after the psum would re-mark the result varying.
    extra = _extra_vma(w, dw)
    if extra:
        dw = lax.psum(dw, extra)
    return (dh32 * g).astype(h.dtype), dw.astype(w.dtype), dy


_xent_sum.defvjp(_xent_sum_fwd, _xent_sum_bwd)


def fused_xent_mean(h, w_embed, targets):
    """Mean token cross-entropy, fully fused.

    ``h``: [B, T, D] final hidden states, ``w_embed``: [V, D] tied
    embedding (fp32 master — cast to the compute dtype in-register),
    ``targets``: [B, T] integer labels.  Returns the scalar mean of
    ``lse - target_logit``; gradients flow to ``h`` and ``w_embed``.
    """
    B, T, D = h.shape
    N = B * T
    br, bv = _blocks(N, w_embed.shape[0])
    h2 = h.reshape(N, D)
    y = targets.reshape(N // br, 1, br).astype(jnp.int32)
    return _xent_sum(h2, w_embed, y, br, bv) / N

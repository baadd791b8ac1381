"""Synchronized batch normalization over a mesh axis.

Reference parity: ``horovod/torch/sync_batch_norm.py`` — there, a torch
module allgathers per-rank sums/counts and hand-writes the backward pass.
TPU-native form: a *function*.  The batch statistics are computed from
local sums + one fused ``psum`` over the data-parallel axis; autodiff
derives the backward (the transpose of psum is psum, so the gradient
cross-shard reduction is automatic and XLA fuses it with the rest of the
backward program).  fp32 statistics regardless of activation dtype.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..scopes import SCOPE_SYNC_BN


def sync_batch_stats(x, axes: Sequence[int] = (0, 1, 2),
                     axis_name: Optional[str] = None
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Mean/variance of ``x`` over ``axes``, synchronized across
    ``axis_name`` shards (one psum of the stacked [sum, sq_sum] pair).

    Returns fp32 ``(mean, var)`` shaped like the remaining axes (biased
    variance, as batch norm uses).
    """
    x32 = x.astype(jnp.float32)
    local = jnp.stack([jnp.sum(x32, axes), jnp.sum(x32 * x32, axes)])
    count = x.size / local[0].size
    if axis_name is not None:
        with jax.named_scope(SCOPE_SYNC_BN):
            local = lax.psum(local, axis_name)
        count = count * lax.axis_size(axis_name)
    s, sq = local
    mean = s / count
    var = sq / count - mean * mean
    return mean, var


def sync_batch_norm(x, scale, bias, running_mean, running_var,
                    axis_name: Optional[str] = None, train: bool = True,
                    momentum: float = 0.9, eps: float = 1e-5):
    """Batch-normalize ``x`` ([..., C], stats over all but the last axis).

    Train mode computes cross-shard batch statistics and returns updated
    running stats; eval mode normalizes with the running stats unchanged.

    Returns ``(y, new_running_mean, new_running_var)`` with y in x's dtype
    and running stats in fp32.
    """
    axes = tuple(range(x.ndim - 1))
    if train:
        mean, var = sync_batch_stats(x, axes, axis_name)
        new_mean = momentum * running_mean + (1.0 - momentum) * mean
        new_var = momentum * running_var + (1.0 - momentum) * var
    else:
        mean, var = running_mean, running_var
        new_mean, new_var = running_mean, running_var
    inv = lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    y = (x.astype(jnp.float32) - mean) * inv + bias.astype(jnp.float32)
    return y.astype(x.dtype), new_mean, new_var

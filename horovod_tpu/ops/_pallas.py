"""What a Pallas file of this package needs that is not its kernel.

The seven kernel files (``flash_attention``, ``grouped_matmul``,
``selective_scan``, ``ssd_scan``, ``mamba2_mixer``, ``kda_scan``, ``rope``)
keep their kernels, their shape rules and their numbers; this holds what they
all said alike: whether a kernel can run here at all (:func:`off_chip`,
the test every ``_refusal`` opens with) and on which dtypes
(:func:`dtype_refusal`), how a refusal is said aloud (:func:`verdict`),
the type of a ``pallas_call``'s result under ``shard_map``
(:func:`sds`), the counter of the calls a file builds
(:func:`kernel_counter`), and what is handed to Mosaic (:func:`params`).
No kernel file imports another.
"""

from __future__ import annotations

import functools
import logging
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from .. import metrics as _metrics

logger = logging.getLogger("horovod_tpu")

# flipped by tests (``pallas_interpret`` in tests/conftest.py) to run the
# kernels on the CPU; read where a call is built, never copied
INTERPRET = False
LANES = 128         # a vector register's width
# what a grid step may hold, and the limit handed to Mosaic where a call
# states no sum of its own: half of a v5e core's 128 MiB of VMEM
STEP_VMEM = 64 * 1024 * 1024
# dimension numbers of ``lax.dot_general``
NT = (((1,), (1,)), ((), ()))      # a @ b^T
TN = (((0,), (0,)), ((), ()))      # a^T @ b


def off_chip() -> Optional[str]:
    """Why no Pallas kernel runs here; None on a TPU backend and where
    the kernels are interpreted."""
    if not INTERPRET and jax.default_backend() != "tpu":
        return f"backend is {jax.default_backend()}, not tpu"
    return None


def dtype_refusal(dtype, *others) -> Optional[str]:
    """Why the kernels refuse operands of ``dtype``; None where it is
    bfloat16 or float32 and the ``others`` are the same."""
    if dtype not in (jnp.bfloat16, jnp.float32) or any(
            d != dtype for d in others):
        return f"dtype {dtype} is neither bfloat16 nor float32"
    return None


def sds(shape, dtype, *operands):
    """ShapeDtypeStruct carrying the union of the operands' varying mesh
    axes — required for pallas_call outputs under shard_map check_vma."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


@functools.lru_cache(maxsize=None)
def _warn_refused(kernel: str, shapes: tuple, reason: str) -> None:
    logger.warning("%s kernel refused shapes %s (%s); falling back to "
                   "the XLA path", kernel, shapes, reason)


def verdict(kernel: str, reason: Optional[str], *operands) -> bool:
    """``reason is None``, said aloud where it matters: on a TPU the XLA
    path is a slower program than the one the caller named, so each
    refused (kernel, shapes, reason) is logged once, at WARNING."""
    if reason is not None and jax.default_backend() == "tpu":
        _warn_refused(kernel, tuple(tuple(x.shape) for x in operands),
                      reason)
    return reason is None


def kernel_counter(name: str, doc: str, labels=("kernel", "path")):
    """``count(*values, n=1)`` over a counter family declared here: ``n``
    more of the series whose ``labels`` hold ``values``, where metrics are
    on."""
    family = _metrics.counter(name, doc, labels=labels)

    def count(*values, n: int = 1) -> None:
        if _metrics.ACTIVE:
            family.inc(n, **dict(zip(labels, values)))

    return count


def params(*semantics, vmem=STEP_VMEM):
    """The ``compiler_params`` of a call whose grid axes have these
    ``dimension_semantics`` (none given: Mosaic's own) and whose step may
    take ``vmem`` bytes."""
    return pltpu.CompilerParams(dimension_semantics=semantics or None,
                                vmem_limit_bytes=int(vmem))

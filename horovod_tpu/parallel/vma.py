"""Varying-manual-axes (VMA) helpers.

The training step runs its shard_map with ``check_vma=True`` so that JAX
tracks replication and emits *correct* psum transposes in the backward
pass (with the check off, gradients through forward psums come out
multiplied by the axis size — a silent ×tp/×pp error this framework hit
and now regression-tests).  The cost of the check is that loop carries
initialized from constants are "invariant" while the loop body makes them
"varying" over a mesh axis; these helpers cast explicitly.
"""

from __future__ import annotations

import jax


def as_varying(tree, axis_name, like=None):
    """Cast every leaf to varying over ``axis_name``.

    ``like`` is an exemplar value that WOULD be varying over the axis when
    VMA tracking is on (e.g. a sharded input): if its vma is empty, the
    surrounding shard_map runs with ``check_vma=False`` and casting would
    poison the (untracked) types — no-op instead.
    """
    if axis_name is None:
        return tree
    if like is not None and axis_name not in jax.typeof(like).vma:
        return tree  # VMA tracking off in this context

    def cast(x):
        if axis_name in jax.typeof(x).vma:
            return x  # already varying over this axis
        try:
            return jax.lax.pcast(x, axis_name, to="varying")
        except ValueError:
            return x

    return jax.tree_util.tree_map(cast, tree)

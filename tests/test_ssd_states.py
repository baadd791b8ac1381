"""ops/ssd_scan.py beside the recurrence (tests/test_ssd_scan.py has the
scan and its six gradients): a strong decay, the states kept at chunk
boundaries, blocks of heads sharing a group's products, and the kernels'
own layout taken and returned (``ssd_scan_turned``)."""

import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import metrics
from horovod_tpu.ops import ssd_scan as sd
from test_ssd_scan import (H, N, P, Q, _close, _counts, _operands, _plain,
                           _states, _value_and_grads)


def test_blocks_of_heads_share_a_groups_products(monkeypatch,
                                                 pallas_interpret):
    """Two groups of eight heads, four heads a grid step: ``B C^T`` made at
    a group's first block, ``dB`` and ``dC`` added up over its two."""
    monkeypatch.setattr(sd, "_HEADS", (4,))
    operands, w = _operands(jnp.float32, 1, 2 * Q, G=2, heads=16, seed=5)
    assert sd._head_block(16, 2) == 4
    value, grads = _value_and_grads(lambda *a: sd.ssd_scan(*a, Q), operands, w)
    want_value, want = _value_and_grads(_plain, operands, w)
    assert abs(float(value - want_value)) <= 1e-5 * abs(float(want_value))
    _close(grads, want, 2e-5)


@pytest.mark.parametrize("path", ["pallas", "xla"])
def test_a_strong_decay_neither_overflows_nor_loses_the_state(path,
        pallas_interpret):
    """``delta A`` near -20 a position: a chunk's running sum passes -300,
    whose exponential is 0 in float32 and whose inverse would be inf;
    every exponent is a difference that is never positive, so nothing
    overflows and nothing is NaN, forward or backward."""
    pallas_interpret(path == "pallas")
    operands, w = _operands(jnp.float32, 1, 4 * Q, seed=2, decay=40.0)
    x, delta, A = operands[:3]
    assert float((delta * A).min()) < -20
    assert float(jnp.cumsum((delta * A)[0, :Q], 0).min()) < -100
    value, grads = _value_and_grads(lambda *a: sd.ssd_scan(*a, Q), operands, w)
    want_value, want = _value_and_grads(_plain, operands, w)
    assert np.isfinite(float(value))
    assert abs(float(value - want_value)) <= 1e-5 * abs(float(want_value))
    _close(grads, want, 1e-3)


def test_state_kept_at_a_chunk_boundary_is_the_sequential_state(
        pallas_interpret):
    operands, _ = _operands(jnp.float32, 2, 4 * Q, seed=3)
    y, bounds = sd._scan_fwd_pallas(*operands[:5], Q)
    want_y, states = _states(*operands[:5], jnp.zeros((H,)))
    np.testing.assert_allclose(y, want_y, rtol=2e-5, atol=2e-5)
    assert bounds.shape == (2, 4, H, P, N) and not np.asarray(bounds[:, 0]).any()
    _, plain_bounds = sd._scan_fwd_xla(*operands[:5], Q)
    for k in range(1, 4):               # chunk k starts from step Q k - 1's state
        np.testing.assert_allclose(bounds[:, k], states[Q * k - 1],
                                   rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(plain_bounds[:, k], states[Q * k - 1],
                                   rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("path", ["pallas", "xla"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_kernels_layout_taken_and_returned_is_the_same_scan(path, dtype,
        pallas_interpret):
    """``ssd_scan_turned`` on ``x^T [Bt, H, P, T]`` is ``ssd_scan`` between
    two transposes: ``y^T`` to the bit (the same kernels, ``D x`` added
    element by element in the other layout) and the six gradients, ``dD``'s
    sum in another order; off the kernels it is that function itself."""
    pallas_interpret(path == "pallas")
    operands, w = _operands(dtype, 2, 4 * Q, G=2)
    turn = lambda a: jnp.transpose(a, (0, 2, 3, 1))
    turned = (turn(operands[0]),) + operands[1:]
    before = _counts()
    value, grads = _value_and_grads(
        lambda *a: sd.ssd_scan_turned(*a, Q), turned, turn(w))
    if metrics.ACTIVE:
        after = _counts()
        assert {k: after[k] - before.get(k, 0) for k in after
                if after[k] != before.get(k, 0)} == {("fwd", path): 1,
                                                     ("bwd", path): 1}
    want_value, want = _value_and_grads(lambda *a: sd.ssd_scan(*a, Q),
                                        operands, w)
    y = sd.ssd_scan(*operands, Q).astype(jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(sd.ssd_scan_turned(*turned, Q).astype(jnp.float32)),
        np.asarray(turn(y)))
    # the same terms added up in another order
    assert abs(float(value - want_value)) <= 1e-6 * float(jnp.abs(y * w).sum())
    _close((jnp.transpose(grads[0], (0, 3, 1, 2)),) + grads[1:], want, 1e-5)

"""ops/kda_scan.py's chunked form in jax.numpy (what runs off the chip and
what the kernels are held to) against the recurrence walked position by
position in float64, over chunk sizes and lengths that are and are not a
multiple of the chunk, and the triangular inverse with its gradient.  (The
kernels are tests/test_kda_scan.py and tests/test_kda_tiles.py.)"""

import jax
import jax.numpy as jnp
import pytest

from horovod_tpu import metrics
from horovod_tpu.ops import kda_scan as kd
from test_kda_scan import CASES, _close, _counts, _got, _operands, _want


@pytest.mark.parametrize("chunk,T", CASES, ids=[f"chunk{c}-T{t}" for c, t in CASES])
def test_chunked_form_and_its_gradients_follow_the_walk(chunk, T):
    operands, w = _operands(T, seed=chunk + T)
    before = _counts()
    value, grads = _got(operands, w, chunk)
    want_value, want_grads, _ = _want(operands, w)
    assert abs(value - want_value) <= 2e-5 * max(1.0, abs(want_value))
    _close(grads, want_grads, 2e-5)
    after = _counts()
    if metrics.ACTIVE:
        assert after.get(("fwd", "xla"), 0) > before.get(("fwd", "xla"), 0)
        assert after.get(("bwd", "xla"), 0) > before.get(("bwd", "xla"), 0)
        assert after.get(("fwd", "pallas"), 0) == before.get(("fwd", "pallas"), 0)


def test_unit_lower_inverse_and_its_gradient():
    L = jnp.tril(jax.random.normal(jax.random.key(0), (3, 16, 16)) * 0.5, -1)
    N = kd._unit_lower_inverse(L)
    eye = jnp.eye(16)
    assert float(jnp.abs(N @ (eye + L) - eye).max()) < 1e-4
    assert float(jnp.abs(jnp.triu(N, 1)).max()) == 0
    w = jax.random.normal(jax.random.key(1), N.shape)
    got = jax.grad(lambda L_: (kd._unit_lower_inverse(L_) * w).sum())(L)
    want = jax.grad(lambda L_: (jnp.linalg.inv(eye + jnp.tril(L_, -1)) * jnp.tril(w)).sum())(L)
    assert float(jnp.abs(got - want).max()) <= 1e-3 * float(jnp.abs(want).max())


def test_off_the_chip_the_kernels_are_refused_by_backend():
    (q, k, v, g, b), _ = _operands(64, Bt=1)
    assert "backend is cpu" in kd._refusal(q, k, v, g, b, 64)
    assert not kd.supported(q, k, v, g, b, 64)

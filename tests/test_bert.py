"""BERT encoder tests (BASELINE config 3).

Reference parity: the reference fine-tunes BERT via DP (SURVEY.md §2.3);
here the native encoder is validated for correctness (masking, TP
equivalence, DP training convergence on the 8-device mesh).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from _helpers import kernels_by_place as _kernels_by_place
from horovod_tpu.models import bert


@pytest.fixture(scope="module")
def cfg():
    return bert.tiny(vocab=64, seq=32, num_labels=3)


@pytest.fixture(scope="module")
def params(cfg):
    return bert.init_params(cfg, jax.random.PRNGKey(0))


def test_shapes_and_determinism(cfg, params):
    tokens = jnp.ones((2, 32), jnp.int32)
    par = bert.ParallelSpec()
    h = bert.encode(params, tokens, cfg, par)
    assert h.shape == (2, 32, cfg.d_model)
    logits = bert.classify(params, tokens, cfg, par)
    assert logits.shape == (2, 3)
    np.testing.assert_allclose(
        np.asarray(logits),
        np.asarray(bert.classify(params, tokens, cfg, par)))


def test_bidirectional_not_causal(cfg, params):
    """Changing a LATE token must change an EARLY position's hidden state
    (encoder is bidirectional, unlike the causal llama)."""
    par = bert.ParallelSpec()
    t1 = jnp.ones((1, 32), jnp.int32)
    t2 = t1.at[0, 30].set(5)
    h1 = bert.encode(params, t1, cfg, par)
    h2 = bert.encode(params, t2, cfg, par)
    assert not np.allclose(np.asarray(h1[0, 0]), np.asarray(h2[0, 0]))


def test_attention_mask_matches_truncated(cfg, params):
    """Masked padding must give the same [CLS] features as physically
    truncating the sequence."""
    par = bert.ParallelSpec()
    rng = np.random.RandomState(0)
    short = jnp.asarray(rng.randint(0, 64, (1, 16)), jnp.int32)
    padded = jnp.concatenate(
        [short, jnp.zeros((1, 16), jnp.int32)], axis=1)
    mask = jnp.concatenate(
        [jnp.ones((1, 16), jnp.int32), jnp.zeros((1, 16), jnp.int32)], 1)
    logits_full = bert.classify(params, short, cfg, par)
    logits_masked = bert.classify(params, padded, cfg, par, mask=mask)
    np.testing.assert_allclose(np.asarray(logits_masked),
                               np.asarray(logits_full), atol=1e-5)


def test_tp_matches_single_device(cfg, params, hvd):
    """Megatron TP over 4 devices must equal the unsharded forward."""
    mesh = jax.make_mesh((4,), ("tp",))
    par_tp = bert.ParallelSpec(tp_axis="tp")
    par_none = bert.ParallelSpec()
    tokens = jnp.asarray(
        np.random.RandomState(1).randint(0, 64, (2, 32)), jnp.int32)
    ref = bert.classify(params, tokens, cfg, par_none)

    specs = bert.param_specs(par_tp, cfg)
    sharded = jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, specs)
    out = jax.jit(jax.shard_map(
        lambda p, t: bert.classify(p, t, cfg, par_tp),
        mesh=mesh, in_specs=(specs, P()), out_specs=P(),
        check_vma=False))(sharded, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4)


def test_sp_ring_matches_single_device(cfg, params, hvd):
    """Non-causal ring attention over sp=4 must equal unsharded."""
    mesh = jax.make_mesh((4,), ("sp",))
    par_sp = bert.ParallelSpec(sp_axis="sp")
    tokens = jnp.asarray(
        np.random.RandomState(2).randint(0, 64, (2, 32)), jnp.int32)
    ref = bert.encode(params, tokens, cfg, bert.ParallelSpec())
    out = jax.jit(jax.shard_map(
        lambda p, t: bert.encode(p, t, cfg, par_sp),
        mesh=mesh, in_specs=(P(), P(None, "sp")),
        out_specs=P(None, "sp"), check_vma=False))(params, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4)


def test_dp_finetune_loss_drops(cfg, hvd):
    """DP fine-tune on the 8-device mesh: loss must drop markedly on the
    synthetic classification set (the config-3 equivalence criterion)."""
    import sys, os
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "examples"))
    from bert_finetune import make_dataset
    import horovod_tpu as hvd_api

    mesh, axis = hvd_api.mesh(), hvd_api.worker_axis()
    params = bert.init_params(cfg, jax.random.PRNGKey(1))
    opt = hvd_api.DistributedOptimizer(optax.adamw(3e-3), axis_name=axis)
    opt_state = jax.jit(opt.init)(params)
    step = bert.make_dp_finetune_step(cfg, mesh, axis, opt)

    tokens, labels = make_dataset(64, 32, cfg.vocab_size, 3, seed=4)
    sh = NamedSharding(mesh, P(axis))
    first = None
    for i in range(30):
        lo = (i * 16) % 48
        x = jax.device_put(jnp.asarray(tokens[lo:lo + 16]), sh)
        y = jax.device_put(jnp.asarray(labels[lo:lo + 16]), sh)
        params, opt_state, loss = step(params, opt_state, x, y)
        if first is None:
            first = float(loss)
    assert float(loss) < first * 0.7, (first, float(loss))


def test_bert_stack_saves_the_packed_kernels_residuals(monkeypatch,
                                                       pallas_interpret):
    """``models/bert.py``'s remat'd encoder keeps dots and the packed
    kernel's two named values: one forward kernel, in the forward scan,
    and the same loss and gradients, to the bit, as under dots alone
    (which reruns the kernel in the remat body)."""
    from horovod_tpu.models import llama
    cfg = bert.BertConfig(vocab_size=64, d_model=128, n_layers=2, n_heads=2,
                          d_ff=128, max_seq_len=128)
    params = bert.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, 128)))
    labels = jnp.asarray([0, 1])

    def grads():
        return jax.jit(jax.value_and_grad(lambda p: bert.loss_fn(
            p, tokens, labels, cfg, llama.ParallelSpec())))

    kept = grads()
    placed = _kernels_by_place(kept, params)
    assert sorted((name, "remat2" in p) for p, name in placed) == [
        ("hvd_flash_bwd", True), ("hvd_flash_fwd", False)]
    got = kept(params)
    cp = jax.checkpoint_policies
    monkeypatch.setattr(cp, "save_only_these_names",
                        lambda *names: cp.nothing_saveable)
    dots_only = grads()
    assert sorted((name, "remat2" in p) for p, name in _kernels_by_place(
        dots_only, params)) == [("hvd_flash_bwd", True),
                                ("hvd_flash_fwd", False),
                                ("hvd_flash_fwd", True)]
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(dots_only(params))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

"""models/hybrid.py's Mamba-2 hybrids (granite-4.0-h-micro): the kinds
``mamba2`` and ``attention`` under the config's frame (RMSNorm, the four
multipliers) against the configuration's plain reference, which walks the
recurrence position by position: benchmark/configs/granite-4.0-h-micro/
reference.py, at its toy sizes.  (The other kinds are tests/test_hybrid.py.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import metrics
from horovod_tpu.models import hybrid, llama
from test_hybrid import _cfg, _mixer_counts, _mixer_grew


def _granite():
    import importlib.util
    import json
    import pathlib
    cdir = (pathlib.Path(__file__).resolve().parents[1] / "benchmark"
            / "configs" / "granite-4.0-h-micro")
    cfg = json.loads((cdir / "config.json").read_text())
    cfg.update(cfg["toy"])
    cfg["dtype"]["compute"] = "float32"
    mods = []
    for name in ("reference", "adapter"):
        spec = importlib.util.spec_from_file_location(
            f"granite_{name}", cdir / f"{name}.py")
        mods.append(importlib.util.module_from_spec(spec))
        spec.loader.exec_module(mods[-1])
    return cfg, mods[0], mods[1]


def _granite_seeded(cfg, ref):
    """Seeded weights under the reference's names, and rows."""
    key = jax.random.key(7)
    return (ref.make_weights(cfg, key),
            ref.make_samples(cfg, jax.random.fold_in(key, 1), 2))


def _granite_program(cfg, adapter, w, batch, lcfg=None, grads=False):
    """The program's loss on the reference's weights and rows; with
    ``grads`` the gradients too, leaf by leaf under the reference's
    names."""
    import dataclasses
    lcfg = lcfg or adapter.program_config(cfg)
    assert dataclasses.is_dataclass(lcfg)
    fn = lambda p: llama.loss_fn(p, *batch, lcfg, llama.ParallelSpec())
    with jax.default_matmul_precision("highest"):
        if not grads:
            return fn(adapter._to_program(w, cfg))
        loss, got = jax.value_and_grad(fn)(adapter._to_program(w, cfg))
    return loss, adapter._to_flat(got, cfg)


def _granite_reference(cfg, ref, w, batch, grads=False):
    with jax.default_matmul_precision("highest"):
        if grads:
            return jax.value_and_grad(lambda w_: ref.loss(cfg, w_, batch))(w)
        return ref.loss(cfg, w, batch)


# What several cases below compare against is the same whatever flag they
# flip: made once a module, the comparisons stay the cases' own.

@pytest.fixture(scope="module")
def granite_reference():
    """``(cfg, ref, adapter, w, batch, (loss, gradients))``: the plain
    reference's walk of the toy configuration and its gradients."""
    cfg, ref, adapter = _granite()
    w, batch = _granite_seeded(cfg, ref)
    return cfg, ref, adapter, w, batch, _granite_reference(
        cfg, ref, w, batch, grads=True)


@pytest.fixture(scope="module")
def loud_granite():
    """``(cfg, adapter, w, batch, the program's loss, the reference's)``
    with weights large enough for the logits to say something: at the
    configuration's ranges a toy's loss is log(vocabulary) whatever the
    trunk computes."""
    cfg, ref, adapter = _granite()
    cfg.update(initializer_range=0.3, residual_out_range=0.3)
    w, batch = _granite_seeded(cfg, ref)
    return (cfg, adapter, w, batch, _granite_program(cfg, adapter, w, batch),
            _granite_reference(cfg, ref, w, batch))


@pytest.mark.parametrize("interpret,mixer", [
    (False, False), (True, False), (True, True)],
    ids=["xla", "kernels", "mixer-kernels"])
def test_mamba2_and_attention_trunk_follows_the_plain_reference(
        interpret, mixer, monkeypatch, pallas_interpret, granite_reference):
    """Loss and every leaf's gradient: mamba2, attention, mamba2 under
    RMSNorm and the four multipliers; the chunked scan in jax.numpy and
    through ``hvd_ssd_chunk_fwd`` / ``hvd_ssd_chunk_bwd`` in interpret
    mode, four chunks a row; with ``mixer`` the convolution and the gate
    through ``ops/mamba2_mixer.py``'s four kernels too, two blocks of
    positions a row (160 convolved channels cut 128, 16, 16), and ``x``
    and ``y`` handed on turned through ``ssd_scan_turned``."""
    from horovod_tpu.ops import mamba2_mixer as mm
    pallas_interpret(interpret)
    if not mixer:       # the scan's kernels beside the mixer's plain form
        monkeypatch.setattr(mm, "_refusal", lambda *a, **k: "off in this case")
    for name, size in (("_BLOCK", 32), ("_ROWS", 16), ("_TURN", (16, 64))):
        monkeypatch.setattr(mm, name, size)
    mixer_before = _mixer_counts()
    cfg, ref, adapter, w, batch, (want_loss, want) = granite_reference
    lcfg = adapter.program_config(cfg)
    assert lcfg.layer_kinds == ("mamba2", "attention", "mamba2")
    assert [(r[0], r[1], len(r[2])) for r in hybrid._runs(lcfg)] == [
        ("mamba2", 0, 1), ("attention", 0, 1), ("mamba2", 1, 1)]
    before = metrics.registry().to_dict().get("hvd_layer_kind_total", {})
    loss, grads = _granite_program(cfg, adapter, w, batch, grads=True)
    if metrics.ACTIVE:
        count = lambda fam: {s["labels"]["kind"]: s["value"]
                             for s in fam.get("series", [])}
        after = count(metrics.registry().to_dict()["hvd_layer_kind_total"])
        grew = {k: n - count(before).get(k, 0) for k, n in after.items()}
        assert {k: n for k, n in grew.items() if n} == {"mamba2": 2,
                                                        "attention": 1}
        # every chain of the mamba2 layers on the one path (a traced
        # call site counts; the plain form's backward is autodiff's)
        path = "pallas" if mixer else "xla"
        assert set(_mixer_grew(mixer_before)) == {
            (k, path) for k in (("conv_fwd", "conv_bwd", "norm_fwd",
                                 "norm_bwd") if mixer
                                else ("conv_fwd", "norm_fwd"))}
    assert abs(float(loss - want_loss)) < 2e-6 * float(want_loss)
    assert set(grads) == set(want) == set(ref.weight_shapes(cfg))
    for name in want:
        np.testing.assert_allclose(
            grads[name], want[name], rtol=2e-3,
            atol=2e-4 * float(jnp.abs(want[name]).max()), err_msg=name)
    assert llama.count_params(lcfg) == sum(
        int(np.prod(s)) for s in ref.weight_shapes(cfg).values())


@pytest.mark.parametrize("field,family", [
    ("embedding_multiplier", 1.0), ("residual_multiplier", 1.0),
    ("attention_multiplier", 0.0), ("logits_scaling", 1.0)])
def test_each_multiplier_is_the_configs_and_not_the_familys(field, family,
                                                            loud_granite):
    """A multiplier dropped, or left at what the other trunks compute,
    moves the loss away from the reference's; each as published keeps it
    there."""
    import dataclasses
    cfg, adapter, w, batch, got, want = loud_granite
    lcfg = adapter.program_config(cfg)
    assert getattr(llama.LlamaConfig(), field) == family
    assert getattr(lcfg, field) == cfg[field] != family
    assert abs(float(got - want)) < 1e-5 * float(want)
    dropped = _granite_program(
        cfg, adapter, w, batch, dataclasses.replace(lcfg, **{field: family}))
    assert abs(float(dropped - want)) > 1e-4 * float(want), field
    # the trunk of identical layers computes none of them, and says so
    with pytest.raises(ValueError, match="trunk of several kinds"):
        llama.LlamaConfig(**{field: cfg[field]})


def test_the_frame_is_the_configs_rmsnorm_has_no_bias():
    cfg = _cfg(d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, n_layers=2,
               layer_kinds=("mamba2", "attention"), ssm_heads=8, ssm_state=16,
               ssm_chunk=16, trunk_norm="rmsnorm")
    params = llama.init_params(cfg, jax.random.key(0))
    assert "final_norm_bias" not in params
    assert set(params["layers"]["attention"]) == {
        "norm1_w", "norm2_w", "w1", "w2", "wqkv", "wo"}
    assert set(params["layers"]["mamba2"]) == {
        "norm1_w", "norm2_w", "w1", "w2", "in_proj", "conv_w", "conv_b",
        "dt_bias", "A_log", "D", "gate_norm", "out_proj"}
    assert params["layers"]["mamba2"]["in_proj"].shape == (
        1, 64, 128 + 128 + 2 * 16 + 8)
    A = np.exp(np.asarray(params["layers"]["mamba2"]["A_log"]))
    assert (1 <= A).all() and (A <= 16).all() and A.std() > 0
    assert llama.count_params(cfg) == sum(
        x.size for x in jax.tree_util.tree_leaves(params))
    # the same kinds under LayerNorm carry its biases: the frame is a field
    biased = llama.init_params(_cfg(**{**vars(cfg), "trunk_norm": "layernorm"}),
                               jax.random.key(0))
    assert "final_norm_bias" in biased and "norm1_b" in biased["layers"]["mamba2"]
    with pytest.raises(ValueError, match="trunk_norm"):
        _cfg(trunk_norm="batchnorm")
    with pytest.raises(ValueError, match="ssm_heads"):
        hybrid.check(_cfg(n_layers=1, layer_kinds=("mamba2",), ssm_heads=3))

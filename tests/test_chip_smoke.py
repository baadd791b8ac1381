"""chip_smoke.py's phases at ``llama.tiny()`` size on the 8-device CPU
mesh, its refusal to run without a TPU, and the compile-cache helper it
shares with ``hvd.init()``.

The trainer phase takes the XLA attention path here: on jax 0.9.0 the
Pallas interpreter does not trace under ``shard_map(check_vma=True)``,
which is how the train step runs, so the kernel itself stays with
tests/test_flash_attention.py (interpret mode, ``check_vma=False``)."""

import dataclasses
import os
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)  # chip_smoke.py is a script at the repo root

import chip_smoke  # noqa: E402
from horovod_tpu.models import llama  # noqa: E402
from horovod_tpu.runtime import use_compile_cache  # noqa: E402


def test_phases_at_tiny_size(hvd):
    """Runtime, eager plane and trainer phases hold on the CPU mesh, with
    the full-width run's options (full remat with the last layer skipped,
    chunked head, adamw_lp) at toy widths."""
    assert chip_smoke.runtime_phase() in ("native", "python")
    chip_smoke.eager_phase()
    model = dataclasses.replace(
        llama.tiny(seq=64), remat_policy="full", remat_skip_layers=1,
        loss_chunk=32)
    facts = chip_smoke.trainer_phase(chip_smoke.SmokeConfig(
        model=model, per_chip_batch=2, seq=64, steps=3))
    # what main() insists on holds only on a TPU
    assert not facts["flash_supported"] and not facts["tpu_custom_call"]
    assert not facts["interpret"]
    assert len(facts["losses"]) == 3
    assert facts["losses"][-1] < facts["losses"][0]
    assert facts["replicas_identical"]
    assert len(facts["peak_bytes_in_use"]) == jax.device_count() == 8


def test_full_width_is_the_bench_preset_with_a_dividing_chunk():
    cfg = chip_smoke.full_width()
    m = cfg.model
    assert (m.vocab_size, m.d_model, m.n_layers, m.n_heads, m.n_kv_heads,
            m.head_dim, m.d_ff) == (32768, 2048, 16, 16, 8, 128, 8192)
    assert (cfg.seq, cfg.per_chip_batch) == (1024, 8) and cfg.steps >= 6
    assert m.loss_chunk > 0 and cfg.seq % m.loss_chunk == 0


def test_result_line_holds_exactly_the_contract_keys():
    import json

    line = chip_smoke.result_line({
        "platform": "tpu", "kind": "TPU v5 lite", "count": 4,
        "jax": "0.9.0", "compile_cache_dir": "/x"})
    assert "\n" not in line
    assert json.loads(line) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 4}}


def test_main_refuses_a_cpu_backend(capsys):
    with pytest.raises(SystemExit) as exit_info:
        chip_smoke.main()
    assert capsys.readouterr().out == ""  # no result without a chip
    assert exit_info.value.code not in (0, None)
    assert "no TPU" in str(exit_info.value.code)
    assert "'cpu'" in str(exit_info.value.code)


def test_bench_peak_comes_from_device_kind(monkeypatch):
    """bench.py prices a TPU by its device_kind; a kind that is not in
    the table raises instead of being priced as a v5e."""
    import types

    import bench

    def found(kind):
        dev = types.SimpleNamespace(platform="tpu", device_kind=kind)
        monkeypatch.setattr(bench.jax, "devices", lambda: [dev])

    found("TPU v5 lite")
    assert bench.detect_peak() == 197.0
    found("TPU v9 imaginary")
    with pytest.raises(RuntimeError, match="TPU v9 imaginary"):
        bench.detect_peak()


def test_compile_cache_helper(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/outside")
        jax.config.update("jax_compilation_cache_dir", None)
        assert use_compile_cache() == "/placed/outside"
        assert jax.config.jax_compilation_cache_dir is None  # jax's to read

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        first, second = use_compile_cache(), use_compile_cache()
        assert first == second == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)

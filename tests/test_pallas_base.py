"""What ``ops/_pallas.py`` hands to Mosaic for the seven kernel files, call
for call: the one thing moving their scaffolding behind one module could
change in silence.  The table below was written out from the tree before
that move (PR 44's) and passes there as it stands."""

import jax
import jax.numpy as jnp
import pytest

from _helpers import block_diffusion_ranges, pallas_eqns

MiB = 1024 * 1024
BF, F32 = jnp.bfloat16, jnp.float32
sds = jax.ShapeDtypeStruct
PA, AR = "parallel", "arbitrary"


def _flash(B, T, H, Hkv, D, Dv=None, causal=True, mask=None, pair=None):
    """``pair = (H2, D2)``: a second query/key pair ``D2`` wide, ``H2`` key
    heads of it."""
    from horovod_tpu.ops import flash_attention as fa
    q, k, v = (sds((B, T, h, d), BF) for h, d in (
        (H, D), (Hkv, D), (Hkv, Dv or D)))
    second = (sds((B, T, H, pair[1]), BF),
              sds((B, T, pair[0], pair[1]), BF)) if pair else ()
    if callable(mask):
        mask = mask(fa)
    return jax.grad(lambda q, k, v, *second: fa.flash_attention(
        q, k, v, causal=causal, mask=mask,
        pair=second or None).astype(F32).sum(),
        tuple(range(3 + len(second)))), (q, k, v, *second)


def _experts(call, R, D, F, E, tokens):
    from horovod_tpu.models import moe
    from horovod_tpu.ops import grouped_matmul as gm
    xs, wt, sizes = sds((R, D), BF), sds((R,), F32), sds((E,), jnp.int32)
    wg, wu, wd = (sds(s, BF) for s in ((E, D, F), (E, D, F), (E, F, D)))
    if call == "forward":
        return moe._expert_ffn, (xs, wg, wu, wd, wt, sizes)
    if call == "combine":
        return (lambda *a: gm.combine(*a, "out")), (
            sds((R, D), F32), sds((R,), jnp.int32), sizes,
            sds((tokens, D), F32))
    held = [sds(w.shape, F32) for w in (wg, wu, wd)]
    return moe._expert_ffn_grads, (xs, wg, wu, wd, wt, sizes,
                                   sds((R, D), F32), *held)


def _selective_scan():
    from horovod_tpu.ops import selective_scan as ss
    T, Ch, N = 8192, 5120, 16
    operands = (sds((1, T, Ch), BF), sds((1, T, Ch), F32), sds((Ch, N), F32),
                sds((1, T, N), BF), sds((1, T, N), BF), sds((Ch,), F32))
    return jax.grad(lambda *a: ss.selective_scan(*a).astype(F32).sum(),
                    argnums=tuple(range(6))), operands


def _ssd_scan():
    from horovod_tpu.ops import ssd_scan as sd
    T, H, P, N = 8192, 64, 64, 128
    operands = (sds((1, T, H, P), BF), sds((1, T, H), F32), sds((H,), F32),
                sds((1, T, 1, N), BF), sds((1, T, 1, N), BF), sds((H,), F32))
    return jax.grad(lambda *a: sd.ssd_scan(*a, 256).astype(F32).sum(),
                    argnums=tuple(range(6))), operands


def _kda_scan():
    from horovod_tpu.ops import kda_scan as kd
    T, H, K = 8192, 8, 128
    wide = sds((1, T, H, K), BF)
    operands = (wide, wide, wide, sds((1, T, H, K), F32), sds((1, T, H), F32))
    return jax.grad(lambda *a: kd.kda_scan(*a, 64).astype(F32).sum(),
                    argnums=tuple(range(5))), operands


def _mixer(turned):
    from horovod_tpu.ops import mamba2_mixer as mm
    T, sizes = 8192, (4096, 128, 128)
    C, Di = sum(sizes), sizes[0]

    def chain(xBC, conv_w, conv_b, z, gate_w):
        x, B, Cm = mm.conv_silu_split(xBC, conv_w, conv_b, sizes, turned)
        return (mm.gated_rmsnorm(x, z, gate_w, 1e-5, turned).astype(F32).sum()
                + B.astype(F32).sum() + Cm.astype(F32).sum())

    return jax.grad(chain, argnums=tuple(range(5))), (
        sds((1, T, C), BF), sds((4, C), F32), sds((C,), F32),
        sds((1, T, Di), BF), sds((Di,), F32))


def _rope(shape, widths, turned, table):
    from horovod_tpu.ops import rope
    operands = (sds(shape, BF), sds(table, F32), sds(table, F32))
    return jax.grad(lambda a, cos, sin: sum(
        part.astype(F32).sum() for part in rope.split_rotate(
            a, cos, sin, widths, turned))), operands


def _qknorm_rope(shape, table):
    from horovod_tpu.ops import rope
    operands = (sds(shape, BF), sds((128,), BF), sds(table, F32),
                sds(table, F32))
    return jax.grad(lambda a, w, cos, sin: rope.norm_rotate(
        a, w, cos, sin, 1e-6).astype(F32).sum(), (0, 1)), operands


# the shapes of the seven files' ``*_lower_for_the_chip`` cases
CALLS = {
    "flash-packed-bert": lambda: _flash(32, 128, 12, 12, 64, causal=False),
    "flash-packed-gqa": lambda: _flash(2, 256, 8, 2, 128),
    "flash-masked-one-group": lambda: _flash(
        1, 8192, 8, 1, 128, mask=block_diffusion_ranges(4096, 4)),
    "flash-masked-sdar": lambda: _flash(
        2, 8192, 32, 4, 128, mask=block_diffusion_ranges(4096, 4)),
    "flash-masked-llama3-8b": lambda: _flash(1, 8192, 32, 8, 128),
    "flash-masked-phi-window": lambda: _flash(
        1, 8192, 20, 10, 64, 128, mask=lambda fa: fa.window_ranges(8192, 512)),
    "flash-masked-phi-causal": lambda: _flash(
        1, 8192, 20, 10, 64, 128, mask=lambda fa: fa.causal_ranges(8192)),
    # the kanana cell's latent attention: a key and value head a query head
    # and one rotary key for all 32
    "flash-paired-kanana": lambda: _flash(
        1, 16384, 32, 32, 128, mask=lambda fa: fa.causal_ranges(16384),
        pair=(1, 64)),
    "experts-sdar-forward": lambda: _experts(
        "forward", 24576, 2048, 768, 16, 16384),
    "experts-sdar-backward": lambda: _experts(
        "backward", 24576, 2048, 768, 16, 16384),
    "experts-sdar-combine": lambda: _experts(
        "combine", 24576, 2048, 768, 16, 16384),
    "experts-solar-forward": lambda: _experts(
        "forward", 2560, 4096, 1280, 8, 8192),
    "experts-solar-backward": lambda: _experts(
        "backward", 2560, 4096, 1280, 8, 8192),
    "experts-solar-combine": lambda: _experts(
        "combine", 2560, 4096, 1280, 8, 8192),
    "experts-lfm2-backward": lambda: _experts(
        "backward", 24576, 2048, 1792, 8, 16384),
    "selective-scan": _selective_scan,
    "ssd-scan": _ssd_scan,
    "kda-scan": _kda_scan,
    "mixer-rows": lambda: _mixer(False),
    "mixer-turned": lambda: _mixer(True),
    "rope-mellum-qkv": lambda: _rope(
        (1, 16384, 5120), (4096, 512, 512), (True, True, False), (16384, 64)),
    "qknorm-rope-sdar-q": lambda: _qknorm_rope(
        (2, 8192, 4096), (2, 8192, 64)),
    "qknorm-rope-sdar-k": lambda: _qknorm_rope((2, 8192, 512), (2, 8192, 64)),
}

# (kernel, dimension_semantics, vmem_limit_bytes) of every pallas_call the
# case traces, in the jaxpr's order; None = not given, Mosaic's own
HANDED = {
    "flash-packed-bert": [
        ("hvd_flash_fwd", None, None),
        ("hvd_flash_bwd", None, None),
    ],
    "flash-packed-gqa": [
        ("hvd_flash_fwd", None, None),
        ("hvd_flash_bwd", None, None),
    ],
    "flash-masked-one-group": [
        ("hvd_flash_fwd", None, 40632320),
        ("hvd_flash_dq", None, 41943040),
        ("hvd_flash_dkv", None, 32014336),
    ],
    "flash-masked-sdar": [
        ("hvd_flash_fwd", None, 40632320),
        ("hvd_flash_dq", None, 41943040),
        ("hvd_flash_dkv", None, 32014336),
    ],
    "flash-masked-llama3-8b": [
        ("hvd_flash_fwd", None, 40632320),
        ("hvd_flash_dq", None, 41943040),
        ("hvd_flash_dkv", None, 29392896),
    ],
    "flash-masked-phi-window": [
        ("hvd_flash_fwd", None, 35520512),
        ("hvd_flash_dq", None, 35651584),
        ("hvd_flash_dkv", None, 27426816),
    ],
    "flash-masked-phi-causal": [
        ("hvd_flash_fwd", None, 35520512),
        ("hvd_flash_dq", None, 35651584),
        ("hvd_flash_dkv", None, 27426816),
    ],
    # the one backward: its step's blocks twice, its scratch (16.8 MB of
    # them dq and dq2 of a kv head's 16,384 rows in float32), its tiles and
    # 4 MiB, ``flash_attention._dqkv_step_bytes``: 45,154,304, not 64 MiB
    "flash-paired-kanana": [
        ("hvd_flash_fwd", None, 53608448),
        ("hvd_flash_dqkv", None, 40_960_000 + 4 * MiB),
    ],
    "experts-sdar-forward": [
        ("hvd_moe_gmm_gate_up", (AR,), 38535168),
        ("hvd_moe_gmm_down", (AR,), 34342912),
    ],
    "experts-sdar-backward": [
        ("hvd_moe_gmm_gate_up", (AR,), 39321600),
        ("hvd_moe_gmm_dh", (AR,), 35393536),
        ("hvd_moe_gmm_dx", (AR,), 41418752),
        ("hvd_moe_tgmm_gate", (AR,), 54001664),
        ("hvd_moe_tgmm_up", (AR,), 54001664),
        ("hvd_moe_tgmm_down", (AR,), 54001664),
    ],
    "experts-sdar-combine": [
        ("hvd_moe_combine_out", (AR,), 35651584),
    ],
    "experts-solar-forward": [
        ("hvd_moe_gmm_gate_up", (AR,), 76808192),
        ("hvd_moe_gmm_down", (AR,), 60033024),
    ],
    "experts-solar-backward": [
        ("hvd_moe_gmm_gate_up", (AR,), 78118912),
        ("hvd_moe_gmm_dh", (AR,), 61083648),
        ("hvd_moe_gmm_dx", (AR,), 82313216),
        # a block's product in passes (``gm._tgmm_slab``: 1,024 rows of
        # 2,048 x 1,280, 128 of 640 x 4,096): a pass's float32 tile, not
        # the block's, among the temporaries
        ("hvd_moe_tgmm_gate", (AR, AR), 70778880),
        ("hvd_moe_tgmm_up", (AR, AR), 70778880),
        ("hvd_moe_tgmm_down", (AR, AR), 70516736),
    ],
    "experts-solar-combine": [
        ("hvd_moe_combine_out", (AR,), 54525952),
    ],
    # the [2048, 1792] accumulator in and out and twice is 58.7 MB of each
    # tgmm's sum, a pass of 1,024 (896) rows of it 7.3 more
    "experts-lfm2-backward": [
        ("hvd_moe_gmm_gate_up", (AR,), 58195968),
        ("hvd_moe_gmm_dh", (AR,), 49025024),
        ("hvd_moe_gmm_dx", (AR,), 60293120),
        ("hvd_moe_tgmm_gate", (AR,), 90701824),
        ("hvd_moe_tgmm_up", (AR,), 90701824),
        ("hvd_moe_tgmm_down", (AR,), 90701824),
    ],
    "selective-scan": [
        ("hvd_ssm_scan_fwd", (PA, AR, AR), 64 * MiB),
        ("hvd_ssm_scan_bwd", (PA, AR, AR), 64 * MiB),
    ],
    "ssd-scan": [
        ("hvd_ssd_chunk_fwd", (PA, AR, AR), 64 * MiB),
        ("hvd_ssd_chunk_bwd", (PA, AR, AR), 64 * MiB),
    ],
    "kda-scan": [
        ("hvd_kda_tiles_fwd", (PA, PA, PA), 64 * MiB),
        ("hvd_kda_chunk_fwd", (PA, PA, AR), 64 * MiB),
        ("hvd_kda_tiles_fwd", (PA, PA, PA), 64 * MiB),
        ("hvd_kda_chunk_bwd", (PA, PA, AR), 64 * MiB),
        ("hvd_kda_tiles_bwd", (PA, PA, PA), 64 * MiB),
    ],
    "mixer-rows": [
        ("hvd_conv_silu_fwd", (PA, PA), 64 * MiB),
        ("hvd_gated_norm_fwd", (PA, PA), 64 * MiB),
        ("hvd_gated_norm_bwd", (PA, AR), 64 * MiB),
        ("hvd_conv_silu_bwd", (PA, AR), 64 * MiB),
    ],
    "mixer-turned": [
        ("hvd_conv_silu_fwd", (PA, PA), 64 * MiB),
        ("hvd_gated_norm_fwd", (PA, PA), 64 * MiB),
        ("hvd_gated_norm_bwd", (PA, AR), 64 * MiB),
        ("hvd_conv_silu_bwd", (PA, AR), 64 * MiB),
    ],
    "rope-mellum-qkv": [
        ("hvd_rope_fwd", (PA, PA), 64 * MiB),
        ("hvd_rope_bwd", (PA, PA), 64 * MiB),
    ],
    # (a step's blocks twice and 4 MiB: ops/rope.py says why)
    "qknorm-rope-sdar-q": [
        ("hvd_rope_norm_fwd", (PA, PA), 12 * MiB),
        ("hvd_rope_norm_bwd", (PA, PA), 16 * MiB),
    ],
    "qknorm-rope-sdar-k": [
        ("hvd_rope_norm_fwd", (PA, PA), 5 * MiB),
        ("hvd_rope_norm_bwd", (PA, PA), 5 * MiB + MiB // 2),
    ],
}


@pytest.mark.parametrize("case", sorted(CALLS))
def test_what_each_kernel_hands_to_mosaic(case, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn, args = CALLS[case]()
    handed = []
    for eqn in pallas_eqns(fn, *args):
        given = eqn.params["compiler_params"].get("mosaic_tpu")
        handed.append((eqn.params["name"],
                       given and given.dimension_semantics,
                       given and given.vmem_limit_bytes))
        assert not eqn.params["interpret"]
    assert handed == HANDED[case]

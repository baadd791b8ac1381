"""The names the jitted steps and the models put on their parts.

One decision that ``training.py``, the model files and whoever reads a
device trace must agree on, so it imports nothing of the package and
whoever opens a scope imports it (``training`` and ``models.moe`` hand
the names on under their own).  ``jax.named_scope``: metadata only, the
compiled program is the same.  The names reach ``compiled.as_text()`` as
``op_name="jit(..)/../<name>/.."`` and xprof's op names: the forward pass
reads ``jvp(hvd_forward)``, the backward pass
``transpose(jvp(hvd_forward))``.
"""

# The steps' own parts (``training.py``, ``models/bert.py``'s fine-tune step)
SCOPE_FORWARD = "hvd_forward"      # model and loss, inside the differentiated fn
SCOPE_REDUCE = "hvd_reduce"        # explicit gradient scaling / pmeans
SCOPE_OPTIMIZER = "hvd_optimizer"  # optimizer.update + apply_updates
SCOPE_SYNC_BN = "hvd_sync_bn"      # SyncBN's psum of the batch statistics
# The model's own parts, opened in ``models/`` where the work is and so
# under whichever step builder's ``hvd_forward``: a layer is the union of
# its sublayers' scopes, and what is left under ``hvd_forward`` alone is
# the residual adds and what an ``objective=`` from outside computes.
SCOPE_EMBED = "hvd_embed"          # the lookups (and BERT's embedding norm)
SCOPE_ATTENTION = "hvd_attention"  # norm, projections, RoPE, kernels, tp's psum
SCOPE_MLP = "hvd_mlp"              # norm + feed-forward, dense or routed experts
SCOPE_HEAD = "hvd_head"            # final norm, head product, loss; ResNet's pool + fc
SCOPE_STEM = "hvd_stem"            # ResNet: conv1 + BN + max-pool
SCOPE_STAGE = "hvd_stage{}"        # ResNet: a stage's blocks, ``.format(i)``
SCOPE_SSM_MIXER = "hvd_ssm_mixer"  # models/hybrid.py's mixers, after norm1
SCOPE_GMU = "hvd_gmu"
SCOPE_DIFF_ATTENTION = "hvd_diff_attention"
SCOPE_SSD_MIXER = "hvd_ssd_mixer"  # the Mamba-2 mixer: norm1, projections, conv, scan, gated norm
SCOPE_SSD_SCAN = "hvd_ssd_scan"    # inside it: ops/ssd_scan.py's call, whatever computes it
SCOPE_KDA_MIXER = "hvd_kda_mixer"  # Kimi Delta Attention: norm1, projections, conv, scan, gated norm
SCOPE_KDA_SCAN = "hvd_kda_scan"    # inside it: ops/kda_scan.py's call, whatever computes it
SCOPE_WINDOW_ATTENTION = "hvd_window_attention"  # plain GQA under the window ("swa"), as hvd_attention
SCOPE_ROPE = "hvd_rope"            # inside either (or hvd_mla_attention): a kind's rotary table and its products with q and k
SCOPE_MLA_ATTENTION = "hvd_mla_attention"  # latent attention ("mla"): norm1, projections, rotation, kernels, wo
SCOPE_MLA_LATENT = "hvd_mla_latent"  # inside it: u Wkv_a, the split, the latent's norm, c Wkv_b
SCOPE_CONV_MIXER = "hvd_conv_mixer"  # the gated short convolution ("conv"): norm1, in_proj, the chain, out_proj
SCOPE_GATED_CONV = "hvd_gated_conv"  # inside it: B * x, the taps and C *, the elementwise chain alone
# Inside ``hvd_mlp`` where the feed-forward is routed (``models/moe.py``)
SCOPE_ROUTE = "hvd_moe_route"        # router logits, scores, top-k
SCOPE_EXPERTS = "hvd_moe_experts"    # sort, grouped products, combine
SCOPE_SHARED = "hvd_moe_shared"      # the expert every token passes through

"""The models the steps of ``training.py`` and the benchmark's cells run.

``mnist`` (MLP / CNN), ``resnet`` (ResNet-18/50 v1.5 with SyncBN),
``bert`` (a post-LN encoder with a classification head and its own
data-parallel fine-tune step), ``llama`` (a Llama-style decoder trunk:
GQA, RoPE, SwiGLU, optional q/k norm, untied head, chunked or
vocabulary-parallel loss), ``moe`` (the trunk's routed experts: a capacity
path over an ``ep`` axis and a dropless path on the experts a chip holds),
``hybrid`` (a trunk whose layers are of several kinds: Mamba-1 and Mamba-2
mixers, differential attention as window, full and cross, gated memory
units, plain attention without positions; the frame from the config) and
``generate`` (KV-cache decoding).  bf16 compute over fp32 parameters,
stacked layers under ``lax.scan`` where the layers are equal, mesh axes
as hooks (``llama.ParallelSpec``).  Each model names its own parts for
the device trace (``scopes.SCOPE_EMBED`` .. ``SCOPE_STAGE``).
"""

from . import generate, llama, mnist, resnet  # noqa: F401  (bert, moe and
#                                        hybrid are imported where they are used)

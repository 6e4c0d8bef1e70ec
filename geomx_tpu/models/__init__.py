"""Model zoo (flax.linen) — TPU-first replacements for the reference's
gluon model layer (reference: python/mxnet/gluon/model_zoo/ + the example
CNN, examples/cnn.py:56-63).

Decoders by module, not re-exported here: ``transformer`` (GPT-2 style;
``dense_attention``, and beside it ``grouped_attention`` and the blocked
``window_attention`` for grouped queries, ``causal_attention`` and
``window_core``, which run full causal and sliding-window attention as
the Pallas kernels of ``ops.flash_attention`` where ``runs_kernel``
says so (a TPU backend, no mesh, 2,048 positions and more, a window of
512 and more) and as those products elsewhere, ``rotary``
positions over a part of a head or all of it, ``rotary_attention``,
the branch three families share, ``gated_attention``, that branch
times the sigmoid gate two of them have, ``latent_attention``, the
core of latent attention: one shared rotary key in the interleaved
pairing beside a head's non-rotary part, a value head of its own size,
and ``block_diffusion_attention``, a clean and a noised copy of every
sequence under one block mask, with ``rotary`` by position id),
and the eight users of ``moe.sparse_dispatch``:
``moe.MoEBlock`` (top-1), ``olmoe`` (softmax top-8 of 64), ``laguna``
(window and full attention layers with their own head counts, a gated
attention output, a dense first layer, a shared expert beside sigmoid
top-8 of 256), ``qwen3_next`` (three Gated DeltaNet linear-attention
layers on ``ops.gated_delta`` to one gated full-attention layer, a
gated shared expert beside normalised softmax top-10 of 512) and
``mellum`` (three window layers to one full YaRN layer, ungated, every
layer normalised softmax top-8 of 64 with no shared expert) and
``kanana`` (latent attention in every layer, a dense first layer, a
shared expert beside sigmoid top-6 of 128 chosen by score plus a
correction bias that is a buffer, every block rematerialised) and
``sdar`` (block-diffusion training: both copies of a sequence through
every layer, per-head q/k norms, normalised softmax top-8 of 128, the
weighted masked-token loss ``moe.masked_diffusion_loss``) and
``nemotron_h`` (every layer ONE mixer by its letter in the pattern:
Mamba-2 state-space layers on the chunked selective scan of
``ops.ssd``, a shared expert beside sigmoid top-6 of 128 chosen by
score plus a correction bias, ``moe.biased_sigmoid_router``, none of
them gated, ``moe.plain_experts``, and grouped-query attention with no
positional term; every block rematerialised, the head and loss a block
of rows at a time), the last seven as one rank's share of an
expert-parallel layout; and ``ouro``, a
looped dense decoder on the same ``rotary`` and ``causal_core``: one
stack of sandwich-normed layers applied ``total_ut_steps`` times a pass
as ONE ``scan`` with the parameters broadcast (a program holds each
block once), every block and every exit rematerialised, an exit gate
after every pass and the expected-exit loss ``ouro.looped_exit_loss``,
as a rank's share of the vocabulary.
"""

from geomx_tpu.models.cnn import LeNetCNN, create_cnn  # noqa: F401
from geomx_tpu.models.mlp import MLP  # noqa: F401
from geomx_tpu.models.resnet import ResNet, create_resnet  # noqa: F401
from geomx_tpu.models.zoo import (  # noqa: F401
    AlexNet, DenseNet, InceptionV3, MobileNetV1, MobileNetV2, SqueezeNet,
    VGG, get_model)

"""Reference-parity model zoo, TPU-first.

The reference ships models only as examples (reference: examples/pytorch/
pytorch_mnist.py, pytorch_imagenet_resnet50.py, tensorflow2/
tensorflow2_keras_mnist.py + synthetic benchmarks, SURVEY §6). Here they are a
first-class package because the driver benchmarks the framework through them:

- ``mlp``         — MNIST MLP (pytorch_mnist.py Net equivalent).
- ``resnet``      — ResNet-50 v1.5, the headline benchmark workload
                    (pytorch_imagenet_resnet50.py / tf_cnn_benchmarks).
- ``transformer`` — flagship Transformer LM exercising every parallelism axis
                    (DP/TP/PP/SP/EP) — the reference has only the primitives
                    for these (SURVEY §2.4); we ship the full stack.
- ``longcat_flash`` — the shortcut-MoE layer with latent attention (two MLA
                    blocks, two dense SwiGLU FFNs and a top-k expert block
                    with zero-compute experts a layer), served through
                    ``ServeEngine`` as one chip's share of its experts.
- ``granite_hybrid`` — layers of two kinds in one stack (Mamba-2 state-space
                    layers, one grouped-query attention layer without
                    positional embedding among every few), each with routed
                    experts and a shared expert; served with a per-slot
                    recurrent state beside the paged KV pool.
- ``solar_open2`` — gated delta-rule linear-attention layers (KDA: a decay a
                    key channel and a rank-one correction of a matrix state
                    a head and slot) with one gated grouped-query attention
                    layer among every few, sigmoid-routed experts and a
                    shared expert; served on ``granite_hybrid``'s step.
- ``olmo_hybrid`` — gated DeltaNet layers (a delta rule with one decay a
                    head and rectangular state heads) with one full attention
                    layer among every four, a dense SwiGLU after each, every
                    sublayer normed after it; served on ``granite_hybrid``'s
                    step and ``delta_rule``'s rule.
- ``mla``         — multi-head latent attention over a paged latent cache and
                    the step the latent-attention models serve on.
- ``kimi_k2``     — the DeepSeek-V3 stack (Kimi K2): latent attention under a
                    YaRN-stretched rotary, a leading dense layer, then
                    sigmoid-routed experts plus a shared expert, served as one
                    chip's share of its experts.
"""

from horovod_tpu.models.mlp import MLP, MnistCNN  # noqa: F401
from horovod_tpu.models.resnet import (  # noqa: F401
    ResNet, ResNet18, ResNet50, ResNet101)
from horovod_tpu.models.vgg import VGG, VGG11, VGG16, VGG19  # noqa: F401
from horovod_tpu.models.inception import InceptionV3  # noqa: F401
from horovod_tpu.models.fused_block import (  # noqa: F401
    fused_to_plain_variables, plain_to_fused_variables)
from horovod_tpu.models.transformer import (  # noqa: F401
    TransformerConfig,
    TransformerLM,
)
from horovod_tpu.models.longcat_flash import LongCatFlashConfig  # noqa: F401
from horovod_tpu.models.granite_hybrid import GraniteHybridConfig  # noqa: F401
from horovod_tpu.models.solar_open2 import SolarOpen2Config  # noqa: F401
from horovod_tpu.models.kimi_k2 import KimiK2Config  # noqa: F401
from horovod_tpu.models.olmo_hybrid import OlmoHybridConfig  # noqa: F401

"""Model zoo (flax.linen), mirroring the reference's example models
(``examples/mnist``, ``examples/imagenet/models/resnet50.py``,
``examples/seq2seq``) as first-class library models."""

from chainermn_tpu.models.mlp import MLP, classification_loss, classification_metrics
from chainermn_tpu.models.resnet import (
    ResNet,
    ResNet18,
    ResNetTiny,
    ResNet50,
    resnet_loss,
)
from chainermn_tpu.models.seq2seq import (
    Seq2Seq,
    TransformerSeq2Seq,
    beam_decode,
    greedy_decode,
    seq2seq_loss,
)
from chainermn_tpu.models.vgg import (
    VGGHead,
    VGGStage,
    apply_sequential,
    build_chain,
    init_stage_params,
    vgg_stage_modules,
)
from chainermn_tpu.models.dcgan import (
    Discriminator,
    GanState,
    Generator,
    gan_init,
    make_gan_train_step,
)
from chainermn_tpu.models.parallel_convnet import (
    channel_parallel_apply,
    channel_parallel_loss,
    channel_parallel_specs,
    dense_reference_apply,
    init_channel_parallel,
    make_channel_parallel_train_step,
)
from chainermn_tpu.models.vit import ViT, vit_loss
from chainermn_tpu.models.transformer import (
    ParallelLM,
    ParallelLMConfig,
    TransformerLM,
    dense_lm_reference,
    init_parallel_lm,
    lm_generate,
    lm_head_logits,
    lm_loss,
    lm_loss_chunked,
    parallel_lm_specs,
)
from chainermn_tpu.models.hybrid import HybridLM
from chainermn_tpu.models.decoding import (
    lm_beam_search,
    lm_speculative_generate,
)
from chainermn_tpu.models.lora import (
    lora_init,
    lora_merge,
    lora_param_count,
    make_lora_loss,
)

__all__ = [
    "MLP",
    "classification_loss",
    "classification_metrics",
    "ResNet",
    "ResNet18",
    "ResNetTiny",
    "ResNet50",
    "ViT",
    "vit_loss",
    "resnet_loss",
    "VGGStage",
    "VGGHead",
    "vgg_stage_modules",
    "init_stage_params",
    "apply_sequential",
    "build_chain",
    "Seq2Seq",
    "TransformerSeq2Seq",
    "seq2seq_loss",
    "beam_decode",
    "greedy_decode",
    "TransformerLM",
    "HybridLM",
    "lm_generate",
    "lm_head_logits",
    "lm_beam_search",
    "lm_speculative_generate",
    "lm_loss",
    "lm_loss_chunked",
    "lora_init",
    "lora_merge",
    "lora_param_count",
    "make_lora_loss",
    "ParallelLM",
    "ParallelLMConfig",
    "init_parallel_lm",
    "parallel_lm_specs",
    "dense_lm_reference",
    "Generator",
    "Discriminator",
    "GanState",
    "gan_init",
    "make_gan_train_step",
    "init_channel_parallel",
    "channel_parallel_specs",
    "channel_parallel_apply",
    "channel_parallel_loss",
    "dense_reference_apply",
    "make_channel_parallel_train_step",
]

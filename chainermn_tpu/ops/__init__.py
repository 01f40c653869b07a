"""TPU kernels (Pallas) for the hot ops.

The reference's hot-op layer was CUDA-side: cupy kernels fused into NCCL
pack/unpack (``pure_nccl_communicator.py``'s fp16 cast-pack) and cuDNN conv/
attention under Chainer.  Here the hot ops are Pallas TPU kernels; everything
has an XLA fallback so the package stays portable (CPU tests run the same
code in interpret mode).
"""

from chainermn_tpu.ops.chunked_ce import chunked_softmax_cross_entropy
from chainermn_tpu.ops.decode_attention import (
    MAX_VERIFY_T,
    paged_decode_attention,
    paged_kernel_takes,
    sharded_paged_decode_attention,
)
from chainermn_tpu.ops.rope import apply_rope
from chainermn_tpu.ops.augment import (
    random_crop,
    random_crop_flip,
    random_flip,
)
from chainermn_tpu.ops.flash_attention import (
    FLASH_MIN_SEQ,
    FLASH_MIN_SEQ_NONCAUSAL,
    flash_attention,
    flash_attention_lse,
    reference_attention,
    resolve_attention,
)
from chainermn_tpu.ops.pooling import max_pool_fused

__all__ = [
    "flash_attention",
    "flash_attention_lse",
    "reference_attention",
    "resolve_attention",
    "FLASH_MIN_SEQ",
    "FLASH_MIN_SEQ_NONCAUSAL",
    "max_pool_fused",
    "paged_decode_attention",
    "paged_kernel_takes",
    "sharded_paged_decode_attention",
    "MAX_VERIFY_T",
    "chunked_softmax_cross_entropy",
    "apply_rope",
    "random_crop",
    "random_crop_flip",
    "random_flip",
]

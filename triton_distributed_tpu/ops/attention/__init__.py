"""Attention kernels: flash prefill, GQA flash-decode, distributed decode,
sequence-parallel attention.

Parity: reference ``kernels/nvidia/flash_decode.py`` (split-KV :130,
combine :393/:482), ``sp_ag_attention_{intra,inter}_node.py``, plus ring
attention as the TPU-native long-context addition (SURVEY.md §5).
"""

from triton_distributed_tpu.ops.attention.flash_attention import (  # noqa: F401
    flash_attention,
    mha_reference,
)
from triton_distributed_tpu.ops.attention.flash_decode import (  # noqa: F401
    flash_decode,
    gqa_decode_reference,
    distributed_flash_decode,
    distributed_flash_decode_2level,
    paged_decode_walk,
    paged_flash_decode,
)
from triton_distributed_tpu.ops.attention.sp_ag_attention import (  # noqa: F401
    sp_ag_attention,
    sp_ag_attention_2level,
)
from triton_distributed_tpu.ops.attention.ring_attention import (  # noqa: F401
    ring_attention,
)

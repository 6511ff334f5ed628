"""State-space kernels: the decode step's recurrent state update, in
place over the engine's per-slot state (docs/serving.md "Recurrent
state beside pages")."""

from triton_distributed_tpu.ops.ssm.decode import (  # noqa: F401
    ssm_decode,
    ssm_decode_reference,
)

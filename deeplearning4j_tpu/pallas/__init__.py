"""Hand-written Pallas TPU kernels for the hot ops.

The role libnd4j's native op library played for the reference
(deeplearning4j-core/pom.xml:154-158 pulls nd4j native backends): ops where
the XLA-fused default leaves performance or memory on the table get a
hand-scheduled kernel. Currently: flash attention (blockwise online
softmax, O(block) memory instead of O(t^2)), the serving decode
attention that reads the KV pool in place (``decode_attention.py``), the
routed experts that fetch only the experts a step's rows reached
(``reached_experts.py``) and the delta-rule recurrence of a decode step
over the slots that owe a token, in place (``delta_step.py``).
"""

from deeplearning4j_tpu.pallas.decode_attention import (  # noqa: F401
    pool_decode_attention,
)
from deeplearning4j_tpu.pallas.flash_attention import (  # noqa: F401
    flash_attention,
    flash_attention_fwd,
    flash_default_interpret,
)

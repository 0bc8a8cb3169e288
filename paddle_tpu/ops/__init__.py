"""Custom TPU kernels (Pallas) and fused ops.

TPU-native analogue of the reference's operators/fused/ — but only where XLA
doesn't already fuse well (SURVEY.md §7: attention, fused optimizer update).
"""
from . import flash_attention  # noqa: F401
from . import latent_attention  # noqa: F401
from . import paged_attention  # noqa: F401
from . import ring_attention  # noqa: F401

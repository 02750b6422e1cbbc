from repro.kernels.paged_attn.kernel import (  # noqa: F401
    page_extent, pages_per_block, walked_pages,
)
from repro.kernels.paged_attn.ops import paged_attention_fused  # noqa: F401

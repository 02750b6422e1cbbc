"""How each kernel shows in a device trace: the event names of its calls.

The paged-attention ``pallas_call`` carries no ``name=``; XLA names its
custom call after the jitted wrapper, ``paged_attention_fused.<n>`` (read
off the compiler's message for that instruction on a v5e, PR 12).
"""
import re

PAGED_ATTN = re.compile(r"^paged_attention")


def kernel_seconds(trace: dict, pattern) -> float:
    """Device seconds of the operations whose label matches ``pattern``
    (mean over devices, inside the traced window)."""
    return sum(s for name, s in trace["ops"].items() if pattern.search(name))

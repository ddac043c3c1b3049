"""Share of its roofline the prefill's flash-attention kernel reaches: the
least time the chip could take for the causal attention of the traced
prefill (the larger of its FLOPs over the bf16 peak and its bytes over the
HBM bandwidth, counts/<model>.py) over the kernel's summed device time."""

import roofline


def read(view):
    return roofline.share(view, "flash_attention")

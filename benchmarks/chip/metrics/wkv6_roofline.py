"""Share of its roofline the prefill's WKV6 kernel reaches: the least time
the chip could take for the traced prefill's WKV recurrence (the larger of
its FLOPs over the bf16 peak and its bytes over the HBM bandwidth,
counts/rwkv6.py) over the kernel's summed device time."""

import roofline


def read(view):
    return roofline.share(view, "wkv6")

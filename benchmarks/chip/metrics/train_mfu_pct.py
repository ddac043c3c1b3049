"""Model FLOPs of the traced training steps (6 N per token plus attention,
from the published widths, counts/<model>.py) over the traced window, as a
share of the chip's bf16 peak."""


def read(view):
    return 100.0 * view.facts["traced_flops"] / (
        view.trace.window_s * view.peaks["bf16_flops_per_s"])

"""Share of the traced training steps in which no op ran on the chip."""


def read(view):
    return 100.0 * view.trace.idle_share

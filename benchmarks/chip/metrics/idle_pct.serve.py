"""Share of the traced serving batch in which no op ran on the chip."""


def read(view):
    return 100.0 * view.trace.idle_share

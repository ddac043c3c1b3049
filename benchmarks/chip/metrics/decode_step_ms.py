"""Device time of one decode step: the programs dispatched in each
``serve.decode_step`` span (the model's ``decode_step`` and the greedy pick),
summed from the trace, per step."""


def read(view):
    steps = view.trace.span_count("serve.decode_step")
    if not steps:
        return None
    return 1e3 * view.trace.module_seconds("serve.decode_step") / steps

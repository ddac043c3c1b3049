"""Device time of the optimizer in one training step, in ms: the ops under
``train_step/optimizer`` (global-norm clipping and the AdamW update), per run
of the training step's program (scopes.py)."""
import scopes


def read(view):
    s = scopes.load().seconds("train_step", "optimizer")
    return None if s is None else 1e3 * s

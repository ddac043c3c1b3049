"""Device time of the WKV recurrence in one prefill, in ms: the ops under
``prefill/layers/.../time_mix/wkv``, which are the named WKV6 kernel and the
layout changes into and out of it, per run of the prefill program
(scopes.py)."""
import scopes


def read(view):
    s = scopes.load().seconds("prefill", "layers", "time_mix", "wkv")
    return None if s is None else 1e3 * s

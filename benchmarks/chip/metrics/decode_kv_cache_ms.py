"""Device time of the KV cache in one decode step, in ms: the ops under
``decode/layers/.../attn/kv_cache`` (the cache write, and the mask read from
the cache's positions) and the ops under ``decode/layers`` outside every
sublayer's scope (the layer scan's stacking and carry of the cache), per run
of the decode program (scopes.py).  The split is printed."""
import sys

import scopes


def read(view):
    found = scopes.load()
    cache = found.seconds("decode", "layers", "attn", "kv_cache")
    if cache is None:
        return None
    scan = found.seconds("decode", "layers", outside=scopes.SUBLAYERS)
    print(f"[scopes] decode_kv_cache_ms: kv_cache {1e3 * cache:.4f} ms + the layer scan "
          f"outside the sublayers {1e3 * scan:.4f} ms a step", file=sys.stderr, flush=True)
    return 1e3 * (cache + scan)

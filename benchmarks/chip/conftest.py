import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

# CPU programs of the tests go to a cache of their own, away from the chip's
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      str(HERE.parents[1] / ".jax_cache" / "cpu-tests"))

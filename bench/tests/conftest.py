"""The benchmark's own tests (outside the repository's test paths):
``python -m pytest bench/tests`` from the checkout root, on the CPU."""
import os
import sys
import tempfile

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


# CPU test programs go to a temporary persistent cache, never the
# checkout's (which holds the chip's); set before JAX is imported
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      tempfile.mkdtemp(prefix="bench-tests-jax-cache-"))

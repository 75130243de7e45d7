import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
# compiles of these tests stay out of the checkout's cache
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(
    os.environ.get("TMPDIR", "/tmp"), "benchmark-tests-jax-cache"))

"""The harness's self-tests run on the CPU (`python -m pytest bench/tests`):
the chip check is bypassed only inside the tests that drive a cell."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [p for p in (ROOT, os.path.join(ROOT, "src")) if p not in sys.path]

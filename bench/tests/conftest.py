import os
import sys

# The benchmark's tests run on the CPU, at small sizes, importing the
# program from the checkout's src/ as bench/run.py does.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

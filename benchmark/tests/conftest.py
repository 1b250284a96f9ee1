"""The benchmark's own tests run on the CPU, on the program's pure-Python
verification backend where a run is driven end to end."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("LIGHTHOUSE_TPU_COMPILE_CACHE", "0")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

"""The harness's own tests run on the CPU at tiny sizes:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))

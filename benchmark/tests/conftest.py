import os
import sys

# `python -m pytest benchmark/tests -q` from the root of the checkout
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

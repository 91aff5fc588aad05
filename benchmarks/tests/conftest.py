"""The benchmark's own checks run on the CPU, at the tiny size each
configuration's file names for rehearsals: ``JAX_PLATFORMS=cpu python3 -m
pytest benchmarks/tests``. They are not part of the repo's ``tests/``."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

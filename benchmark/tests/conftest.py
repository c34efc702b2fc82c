"""The benchmark's own tests run on the CPU, at sizes a test run holds:
``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_ENABLE_X64", "1")
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

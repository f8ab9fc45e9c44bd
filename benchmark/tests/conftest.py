"""The benchmark's own tests (not part of the repo's `tests/`):

    python3 -m pytest benchmark/tests -q

Everything here runs on the CPU at the `tiny` widths.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))  # run.py
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))  # the repo

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

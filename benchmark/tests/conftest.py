"""The benchmark's own tests run on the CPU, whatever the shell exports:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

They are not under ``tests/``, so the repo's tier-1 count does not move."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_ALS_PLAN_CACHE", "off")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_compilation_cache", False)

"""The benchmark's own tests: run by hand (``python -m pytest
perfbench/tests -q``) and in the CPU rehearsal, not part of tier-1.
They pin JAX to the CPU with four virtual devices and interpret the
Pallas kernels, like ``perfbench/rehearse.py``."""
import os
import sys

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")
for _k in ("FLASH", "NORM", "CE", "DECODE"):
    os.environ.setdefault(f"MXNET_TPU_{_k}_INTERPRET", "1")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

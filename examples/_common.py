"""Shared example bootstrap.

`setup()` makes the repo importable and — when JAX_PLATFORMS=cpu is set —
asks for a virtual CPU mesh (PADDLE_TPU_VIRTUAL_DEVICES, default 8) before
anything starts the backend. Call it before the first jax operation.
"""
import os
import sys


def setup():
    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        import jax

        jax.config.update(
            "jax_num_cpu_devices",
            int(os.environ.get("PADDLE_TPU_VIRTUAL_DEVICES", "8")))

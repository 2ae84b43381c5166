"""Pallas kernels for the paper's hot spots, each as kernel.py (the TPU
kernel), ops.py (the public wrapper) and ref.py (the pure-jnp oracle)."""
from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """True on the CPU, where kernels run in Pallas interpret mode; False
    on the TPU they are written for.  Any other platform is an error:
    no silent fallback hides which device ran the kernel."""
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(f"Pallas kernels run on 'tpu' (or interpreted on "
                       f"'cpu'), not on {platform!r}")

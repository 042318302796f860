"""Where the Pallas kernels run: compiled by Mosaic, or interpreted.

The one decision for every SLA kernel entry point and backend: the
interpreter runs only when JAX's default backend is the CPU (tests and
CPU-only hosts). Anywhere else the kernels are compiled, so nothing on
the SLA path runs interpreted on a TPU host.
"""
from __future__ import annotations

import jax


def default_interpret() -> bool:
    """True iff the Pallas kernels must run in interpret mode here."""
    return jax.default_backend() == "cpu"

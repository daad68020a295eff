"""Shared accelerator-backend probe for the Pallas kernel modules.

Every Pallas entry point in this repo picks compiled-vs-interpret mode
from the same question — "is the default JAX backend a real TPU?" — and
one probe answers it here.  On a TPU backend every kernel compiles for
the chip; interpret mode is only for backends with no Pallas TPU
lowering (the CPU the tests run on).  The probe raises whatever the
backend raises: a backend that cannot be queried is an error, never a
reason to run a kernel interpreted.
"""

from __future__ import annotations

import jax


def on_tpu() -> bool:
    """True when kernels compile for a real TPU."""
    return jax.default_backend() == "tpu"


def default_interpret() -> bool:
    """The ``interpret=`` default for every pallas_call in this repo."""
    return not on_tpu()

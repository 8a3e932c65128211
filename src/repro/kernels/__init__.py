"""Pallas kernels of the served path (paged decode, paged chunk prefill,
flash attention) and their jitted wrappers (``ops``)."""
from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """Whether Pallas kernels run under the interpreter: only off-TPU.

    The one place the choice is made. Every kernel entry point takes
    ``interpret=None`` and asks here, so a caller that omits the argument
    runs the compiled kernel on a TPU backend and the interpreter on the CPU
    (which is how the test suite executes the kernels)."""
    return jax.default_backend() != "tpu"

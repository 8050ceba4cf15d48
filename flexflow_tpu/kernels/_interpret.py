"""The one place that decides compiled vs interpret-mode Pallas."""
from __future__ import annotations

import jax


def pallas_interpret() -> bool:
    """Pallas kernels run compiled (Mosaic) on a TPU backend and in
    interpret mode — plain XLA ops — on the CPU test platform. Decided
    from the platform alone; a backend that fails to initialise raises
    here instead of quietly selecting the interpreter."""
    return jax.default_backend() != "tpu"

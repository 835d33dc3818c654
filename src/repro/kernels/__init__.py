"""Pallas TPU kernels for the STAR pipeline and paged decode.

Every kernel entry point takes ``interpret: Optional[bool] = None`` and
resolves it with ``resolve_interpret``: compiled (Mosaic) when JAX runs on
a TPU, the Pallas interpreter everywhere else. A caller that omits the
argument therefore never runs the interpreter on a chip.
"""

from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """``None`` -> interpret off the TPU, compile on it; a bool wins."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret

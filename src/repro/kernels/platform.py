"""The one platform check behind every Pallas entry point's interpret mode.

The kernels here are written for the TPU. On any other backend (the CPU
hosts the tests run on) they can only execute through the Pallas
interpreter, so ``interpret=None`` — every entry point's default —
resolves to the interpreter there and to the native kernel on a TPU. An
explicit True/False is passed through unchanged.
"""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)

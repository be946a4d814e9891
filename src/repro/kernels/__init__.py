"""Pallas TPU kernels: the paper's microbenchmarks (P-chase, streaming
copy, strided access) and the model's hot spots (flash attention, paged
decode attention, RMSNorm).

Every kernel takes ``interpret``; ``None`` (the default everywhere) means
compile for the chip when the default backend is a TPU and run the kernel
body in the Pallas interpreter otherwise.  A kernel called directly on the
chip therefore never interprets unless asked to.
"""

from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` -> interpret exactly when the default backend is not a TPU."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret

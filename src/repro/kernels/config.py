"""Kernel execution configuration."""

from __future__ import annotations

import jax


def interpret() -> bool:
    """Whether pallas_calls run in interpret mode on the current backend.

    Interpret mode (the kernel body as plain JAX ops, bit-faithful to the
    TPU dataflow) on the CPU backend; Mosaic-compiled kernels on a TPU. No
    other backend has a kernel path, so asking there is an error, not a
    silent fallback.
    """
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"no Pallas kernel path for the {backend!r} backend "
                       "(interpret mode on cpu, Mosaic on tpu)")

"""Shared Pallas utilities: TPU detection, compiler params, VMEM scratch.

Kernels in this package target TPU (Mosaic). Off the TPU they are
validated with ``interpret=True`` — the kernel body executes in Python with
identical semantics, so the allclose-vs-oracle tests exercise the real
tiling/masking logic. ``ops.py`` wrappers pick the mode automatically.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["pl", "pltpu", "on_tpu", "interpret_default", "compiler_params", "vmem_scratch", "NEG_INF"]

NEG_INF = -1e30


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret_default() -> bool:
    """Interpret mode unless running on a real TPU."""
    return not on_tpu()


def compiler_params(dimension_semantics: tuple[str, ...]):
    """Mosaic compiler params (dimension semantics drive pipelining)."""
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics)


def vmem_scratch(shape: tuple[int, ...], dtype=jnp.float32):
    """A VMEM scratch allocation."""
    return pltpu.VMEM(shape, dtype)

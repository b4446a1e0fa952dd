"""Shared kernel utilities: interpret-mode policy and block helpers.

Kernels are written with explicit BlockSpec VMEM tiling for a TPU v5e.
On a TPU backend they compile through Mosaic; on any other backend they
run under ``interpret=True`` (Python execution of the kernel body), which
is how the CPU test suite checks them against the pure-jnp oracles in
each kernel's ``ref.py``.  Interpret mode says nothing about what Mosaic
accepts: ``tests/test_chip_compile.py`` compiles the main-path kernels
for a described v5e to check that.
"""
from __future__ import annotations

import jax

#: Scoped VMEM a kernel may use on v5e without raising
#: ``vmem_limit_bytes`` (the compiler's default limit; the core has
#: 128 MiB of VMEM in all).
VMEM_LIMIT_BYTES = 16 * 1024 * 1024
LANES = 128                            # vreg lanes (minor dim of a tile)
SUBLANE = 8                            # vreg sublanes for 32-bit types


def use_interpret() -> bool:
    """Pallas interpret mode: off on a TPU backend, on everywhere else."""
    return jax.default_backend() != "tpu"


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b

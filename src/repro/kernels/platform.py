"""What every Pallas kernel asks of the platform: interpret or compile, and
how much scoped VMEM to request.

Kernels compile through Mosaic when the default backend is a TPU and run in
the Pallas interpreter everywhere else (CPU tests).  An explicit
``interpret=`` still wins, so tests can compile for a described TPU while
the process itself runs on the CPU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Mosaic's default scoped-VMEM limit; kernels whose blocks fit keep it.
DEFAULT_SCOPED_VMEM = 16 << 20
# Slack above the counted blocks for Mosaic's internal scratch and the
# per-step temporaries of a kernel body: a fixed part plus a quarter of the
# blocks (an f32 dot at HIGHEST precision splits its operands into bf16
# parts; at D=H=1024, B=8 that took 5.1 MiB over 36.3 MiB of blocks).
VMEM_HEADROOM = 4 << 20


def resolve_interpret(interpret: bool | None = None) -> bool:
    """``interpret`` if given, else True off-TPU and False on a TPU."""
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def vmem_bytes(shape, dtype) -> int:
    """VMEM footprint of one buffer: the last two dims padded to the native
    tile (8·(4/itemsize) sublanes × 128 lanes)."""
    item = jnp.dtype(dtype).itemsize
    dims = [int(d) for d in shape] or [1]
    dims[-1] = -(-dims[-1] // 128) * 128
    if len(dims) >= 2:
        sub = 8 * max(1, 4 // item)
        dims[-2] = -(-dims[-2] // sub) * sub
    n = item
    for d in dims:
        n *= d
    return n


def vmem_limit(block_bytes: int) -> int:
    """Scoped-VMEM limit for a kernel whose resident blocks, pipeline
    buffers and scratch add up to ``block_bytes``; never below Mosaic's
    default."""
    return max(DEFAULT_SCOPED_VMEM,
               int(block_bytes) * 5 // 4 + VMEM_HEADROOM)


__all__ = ["DEFAULT_SCOPED_VMEM", "VMEM_HEADROOM", "resolve_interpret",
           "vmem_bytes", "vmem_limit"]

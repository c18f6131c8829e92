"""TPU tiling facts every kernel launch and the static VMEM model share.

Mosaic lays the last two dims of a VMEM buffer out in (sublane, lane)
tiles of (8, 128) 32-bit words (16 rows for 2-byte types, 32 for 1-byte).
A ``BlockSpec`` block must therefore have its last dim a multiple of 128
and its second-to-last a multiple of 8 — or equal to the array's own
dim — and a buffer's footprint is its tile-padded size, not its element
count.
"""
from __future__ import annotations

import math

from jax.experimental.pallas import tpu as pltpu

LANE = 128
SUBLANE = 8

#: Scoped-VMEM request of every kernel in this package, out of the
#: 128 MiB per core of a v5e (the compiler's default scoped limit is
#: 16 MiB); MNIST-width ICT needs 88 MiB by the VMEM model at the
#: smallest legal row tile. ``analysis/vmem`` checks every profiled
#: launch against it.
VMEM_LIMIT_BYTES = 100 * 2**20


def round_up(x: int, b: int) -> int:
    return -(-x // b) * b


def padded_bytes(shape: tuple[int, ...], itemsize: int) -> int:
    """VMEM bytes of a buffer of ``shape``: the last two dims padded to
    whole (sublane, lane) tiles of the element size."""
    if not shape:
        return itemsize
    lanes = round_up(shape[-1], LANE)
    if len(shape) == 1:
        return lanes * itemsize
    sub = SUBLANE * max(1, 4 // itemsize)
    return (math.prod(shape[:-2]) * round_up(shape[-2], sub) * lanes
            * itemsize)


def block_tiling_error(block: tuple[int, ...],
                       array: tuple[int, ...]) -> str | None:
    """Why Mosaic would refuse ``block`` over ``array`` (None if legal)."""
    for dim, mult in ((-1, LANE), (-2, SUBLANE))[:len(block)]:
        if block[dim] % mult and block[dim] != array[dim]:
            return (f"block {block} on array {array}: dim {dim} = "
                    f"{block[dim]} is neither a multiple of {mult} nor "
                    f"the array's {array[dim]}")
    return None


def compiler_params(*semantics: str) -> pltpu.CompilerParams:
    """Grid semantics plus the package-wide scoped-VMEM request."""
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)

"""Shared helpers for the Pallas TPU kernels.

All kernels target TPU (pl.pallas_call + explicit BlockSpec VMEM tiling) and
are validated on CPU with ``interpret=True``.  The scheduler's own kernel
(`repro.kernels.event_resolve`) compiles natively unless its caller asks
for interpret mode; the others still pick it from the default backend
through `use_interpret()`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# TPU tiling constants: (sublane, lane) min tile for f32 is (8, 128); MXU
# native matmul tile is 128x128.
SUBLANE = 8
LANE = 128


def use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def pad_to(x: jnp.ndarray, axis: int, multiple: int, value=0):
    """Pad `axis` of x up to the next multiple; returns (padded, orig_size)."""
    size = x.shape[axis]
    target = -(-size // multiple) * multiple
    if target == size:
        return x, size
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - size)
    return jnp.pad(x, pads, constant_values=value), size


def round_up(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple

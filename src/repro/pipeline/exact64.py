"""Exact IEEE-754 binary64 arithmetic on int64 bit patterns.

The allocation scan (`batch_alloc`) and the circuit calendar
(`batch_circuit`) must reproduce the NumPy oracles bit for bit.  XLA:TPU
has no f64 unit: it carries an f64 as a pair of f32 values (about 48
significant bits against IEEE's 53), so its f64 sums and products differ
from NumPy's in the last bits.  Both device programs therefore carry
every time, size and load as the int64 bit pattern of a non-negative
double, on every backend, and do their few arithmetic steps here with
integer operations, which every backend computes exactly.

For non-negative doubles and +inf the bit pattern is monotone in the
value, so comparisons, min, max, argmin and selects act on the patterns
as they are.  `NEG_INF`, the pattern of -inf, is a negative int64: it
sorts below every one of them and serves as the identity of a max.

`add` and `mul` round to nearest, ties to even, and handle zeros and
subnormals, so each equals the NumPy operation on the same doubles
(`tests/test_exact64.py`).  They need ``jax.enable_x64``.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["INF", "NEG_INF", "ONE", "to_bits", "from_bits", "add", "mul"]

#: Bit patterns of +inf, -inf and 1.0.
INF = int(np.float64(np.inf).view(np.int64))
NEG_INF = int(np.float64(-np.inf).view(np.int64))
ONE = int(np.float64(1.0).view(np.int64))

_FRAC = (1 << 52) - 1
_HIDDEN = 1 << 52


def to_bits(x) -> np.ndarray:
    """Host doubles -> int64 bit patterns (``-0.0`` becomes ``+0.0``)."""
    return (np.asarray(x, dtype=np.float64) + 0.0).view(np.int64)


def from_bits(bits) -> np.ndarray:
    """int64 bit patterns (host or device) -> host doubles."""
    return np.ascontiguousarray(np.asarray(bits), dtype=np.int64).view(
        np.float64
    )


def _u64(x):
    return jax.lax.bitcast_convert_type(x, jnp.uint64)


def _shift_right_sticky(m, k):
    """``m >> k`` with every bit shifted out OR-ed into bit 0 (uint64)."""
    k = jnp.minimum(k, 63).astype(jnp.uint64)
    lost = (m & ((jnp.uint64(1) << k) - jnp.uint64(1))) != 0
    return (m >> k) | lost.astype(jnp.uint64)


def _round_pack(e, r):
    """Round a significand with 3 extra low bits (the last one sticky) to
    nearest even and pack it with biased exponent ``e >= 1``.

    ``r`` holds the leading one at bit 55 (or below it only when ``e``
    is 1: a subnormal).  Adding the significand to ``(e - 1) << 52``
    lets a rounding carry, or a subnormal rounding up to the least
    normal, move into the exponent by itself; an exponent past the
    largest finite one saturates to +inf.
    """
    m = r >> 3
    rem = r & 7
    up = (rem > 4) | ((rem == 4) & ((m & 1) == 1))
    bits = ((e.astype(jnp.uint64) - 1) << 52) + m + up.astype(jnp.uint64)
    return jnp.minimum(bits, jnp.uint64(INF)).astype(jnp.int64)


def _unpack(u):
    """Biased exponent (at least 1) and significand with its hidden bit."""
    e = (u >> 52).astype(jnp.int64)
    m = (u & _FRAC) | jnp.where(e > 0, _HIDDEN, 0).astype(jnp.uint64)
    return jnp.maximum(e, 1), m


def add(a, b):
    """``a + b`` on bit patterns of non-negative doubles (+inf allowed)."""
    hi = _u64(jnp.maximum(a, b))
    lo = _u64(jnp.minimum(a, b))
    eh, mh = _unpack(hi)
    el, ml = _unpack(lo)
    s = (mh << 3) + _shift_right_sticky(ml << 3, eh - el)
    carry = s >> 56  # the sum reached the next binade
    s = jnp.where(carry == 1, _shift_right_sticky(s, 1), s)
    out = _round_pack(eh + carry.astype(jnp.int64), s)
    return jnp.where(hi >> 52 == 2047, jnp.maximum(a, b), out)


def mul(a, b):
    """``a * b`` on bit patterns of non-negative finite doubles."""
    ua, ub = _u64(a), _u64(b)
    ea, ma = _unpack(ua)
    eb, mb = _unpack(ub)

    def normalize(e, m):  # subnormal: move the leading one to bit 52
        sh = jnp.maximum(jax.lax.clz(m).astype(jnp.int64) - 11, 0)
        return e - sh, m << sh.astype(jnp.uint64)

    ea, ma = normalize(ea, ma)
    eb, mb = normalize(eb, mb)
    # 106-bit product hi:lo of two 53-bit significands, from 32-bit halves.
    lo32 = jnp.uint64(0xFFFFFFFF)
    ah, al = ma >> 32, ma & lo32
    bh, bl = mb >> 32, mb & lo32
    ll = al * bl
    mid = ah * bl + al * bh
    lo = ll + (mid << 32)
    hi = ah * bh + (mid >> 32) + (lo < ll).astype(jnp.uint64)
    # Keep 56 bits (leading one at bit 55), the rest sticky.
    top = hi >> 41  # 1 when the product is >= 2**105
    s = jnp.uint64(49) + top
    r = (
        (hi << (jnp.uint64(64) - s))
        | (lo >> s)
        | ((lo & ((jnp.uint64(1) << s) - jnp.uint64(1))) != 0).astype(
            jnp.uint64
        )
    )
    e = ea + eb - 1023 + top.astype(jnp.int64)
    r = _shift_right_sticky(r, jnp.maximum(1 - e, 0))  # subnormal result
    out = _round_pack(jnp.maximum(e, 1), r)
    return jnp.where((ua == 0) | (ub == 0), jnp.int64(0), out)

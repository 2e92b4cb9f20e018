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


# -- the same arithmetic on uint32 halves ------------------------------------
#
# XLA:TPU emulates every int64 or uint64 operation as a pair of 32-bit ones,
# and Mosaic (the Pallas TPU compiler) has no 64-bit integers at all.  The
# allocation scan's Pallas kernel therefore carries each pattern as a
# ``(hi, lo)`` pair of uint32 arrays and runs `add` and `mul` on the pairs:
# the same steps, in the same order, with 64-bit shifts, carries and
# compares written out, and the 53 x 53-bit significand product built from
# 16-bit limbs (a 16 x 16-bit product is exact in uint32).  Every shift
# amount stays in [0, 31], where XLA and Mosaic agree.  These need no x64.

_U32 = jnp.uint32
_HI_INF = INF >> 32  # the high word of +inf's pattern


def split(bits):
    """int64 patterns -> ``(hi, lo)`` uint32 halves (under x64)."""
    u = _u64(jnp.asarray(bits, jnp.int64))
    return (u >> 32).astype(_U32), u.astype(_U32)


def join(x):
    """``(hi, lo)`` uint32 halves -> int64 patterns (under x64)."""
    hi, lo = x
    u = (hi.astype(jnp.uint64) << 32) | lo.astype(jnp.uint64)
    return jax.lax.bitcast_convert_type(u, jnp.int64)


def ge_halves(a, b):
    """``a >= b`` on pairs of non-negative patterns (unsigned compare)."""
    return (a[0] > b[0]) | ((a[0] == b[0]) & (a[1] >= b[1]))


def select_halves(c, a, b):
    """Where ``c``, ``a``, else ``b``, word by word."""
    return jnp.where(c, a[0], b[0]), jnp.where(c, a[1], b[1])


def _add_w(a, b):
    lo = a[1] + b[1]
    return a[0] + b[0] + (lo < a[1]).astype(_U32), lo


def _shr_w(x, s):
    """``x >> s`` for uint32 ``s`` in [0, 63]."""
    hi, lo = x
    big = s >= 32
    t = jnp.where(big, s - 32, s)
    # ``(hi << 1) << (31 - t)`` is ``hi << (32 - t)`` with a shift < 32.
    small_lo = (lo >> t) | ((hi << 1) << (31 - t))
    return jnp.where(big, _U32(0), hi >> t), jnp.where(big, hi >> t, small_lo)


def _shl_w(x, s):
    """``x << s`` for uint32 ``s`` in [0, 63]."""
    hi, lo = x
    big = s >= 32
    t = jnp.where(big, s - 32, s)
    small_hi = (hi << t) | ((lo >> 1) >> (31 - t))
    return jnp.where(big, lo << t, small_hi), jnp.where(big, _U32(0), lo << t)


def _shr_sticky_w(x, k):
    """`_shift_right_sticky` on pairs; ``k`` a non-negative int32."""
    k = jnp.minimum(k, 63).astype(_U32)
    y = _shr_w(x, k)
    back = _shl_w(y, k)
    lost = (back[0] != x[0]) | (back[1] != x[1])
    return y[0], y[1] | lost.astype(_U32)


def _round_pack_w(e, r):
    """`_round_pack` on pairs; ``e`` an int32 >= 1."""
    rh, rl = r
    mh, ml = rh >> 3, (rl >> 3) | (rh << 29)
    rem = rl & 7
    up = (rem > 4) | ((rem == 4) & ((ml & 1) == 1))
    lo = ml + up.astype(_U32)
    hi = ((e - 1).astype(_U32) << 20) + mh + (lo < ml).astype(_U32)
    over = hi >= _HI_INF  # at or past +inf: saturate
    return jnp.where(over, _U32(_HI_INF), hi), jnp.where(over, _U32(0), lo)


def _unpack_w(x):
    hi, lo = x
    e = (hi >> 20).astype(jnp.int32)
    mh = (hi & 0xFFFFF) | jnp.where(e > 0, _U32(1 << 20), _U32(0))
    return jnp.maximum(e, 1), (mh, lo)


def _clz_w(x):
    """Leading zeros of a uint32, by binary search (Mosaic has no clz)."""
    n = jnp.zeros(x.shape, jnp.int32)
    for s in (16, 8, 4, 2, 1):
        small = (x >> (32 - s)) == 0
        n = n + jnp.where(small, jnp.int32(s), jnp.int32(0))
        x = jnp.where(small, x << s, x)
    return n + (x == 0).astype(jnp.int32)


def _mul_w(x, y):
    """Full 64-bit product of two uint32 arrays, from 16-bit limbs."""
    x1, x0 = x >> 16, x & 0xFFFF
    y1, y0 = y >> 16, y & 0xFFFF
    p00, p11 = x0 * y0, x1 * y1
    p01 = x0 * y1
    mid = p01 + x1 * y0
    lo = p00 + (mid << 16)
    hi = (
        p11 + (mid >> 16) + ((mid < p01).astype(_U32) << 16)
        + (lo < p00).astype(_U32)
    )
    return hi, lo


def add_halves(a, b):
    """`add` on ``(hi, lo)`` uint32 pairs."""
    a_hi = ge_halves(a, b)
    hi = select_halves(a_hi, a, b)
    lo = select_halves(a_hi, b, a)
    eh, mh = _unpack_w(hi)
    el, ml = _unpack_w(lo)
    three = _U32(3)
    s = _add_w(_shl_w(mh, three), _shr_sticky_w(_shl_w(ml, three), eh - el))
    carry = s[0] >> 24  # the sum reached the next binade
    s = select_halves(carry == 1, _shr_sticky_w(s, jnp.int32(1)), s)
    out = _round_pack_w(eh + carry.astype(jnp.int32), s)
    return select_halves((hi[0] >> 20) == 2047, hi, out)


def mul_halves(a, b):
    """`mul` on ``(hi, lo)`` uint32 pairs."""
    ea, ma = _unpack_w(a)
    eb, mb = _unpack_w(b)

    def normalize(e, m):  # subnormal: move the leading one to bit 52
        clz = jnp.where(m[0] != 0, _clz_w(m[0]), 32 + _clz_w(m[1]))
        sh = jnp.maximum(clz - 11, 0)
        return e - sh, _shl_w(m, sh.astype(_U32))

    ea, ma = normalize(ea, ma)
    eb, mb = normalize(eb, mb)
    # 106-bit product in four words w3:w2:w1:w0 from 32 x 32-bit parts.
    ll = _mul_w(ma[1], mb[1])
    lh = _mul_w(ma[1], mb[0])
    hl = _mul_w(ma[0], mb[1])
    hh = _mul_w(ma[0], mb[0])
    t = ll[0] + lh[1]
    w1 = t + hl[1]
    c1 = (t < ll[0]).astype(_U32) + (w1 < t).astype(_U32)
    u = hh[1] + lh[0]
    u2 = u + hl[0]
    w2 = u2 + c1
    c2 = (
        (u < hh[1]).astype(_U32) + (u2 < u).astype(_U32)
        + (w2 < u2).astype(_U32)
    )
    w3 = hh[0] + c2
    # Keep 56 bits (leading one at bit 55), the rest sticky: the product
    # shifted right by 49 + top, 32 of them the dropped word w0.
    top = w3 >> 9  # 1 when the product is >= 2**105
    sh = 17 + top
    sticky = (ll[1] != 0) | ((w1 & ((_U32(1) << sh) - 1)) != 0)
    r = (
        (w2 >> sh) | (w3 << (32 - sh)),
        (w1 >> sh) | (w2 << (32 - sh)) | sticky.astype(_U32),
    )
    e = ea + eb - 1023 + top.astype(jnp.int32)
    r = _shr_sticky_w(r, jnp.maximum(1 - e, 0))  # subnormal result
    out = _round_pack_w(jnp.maximum(e, 1), r)
    zero = ((a[0] == 0) & (a[1] == 0)) | ((b[0] == 0) & (b[1] == 0))
    zero_w = _U32(0)
    return jnp.where(zero, zero_w, out[0]), jnp.where(zero, zero_w, out[1])

"""Integer-only binary64 arithmetic (`repro.pipeline.exact64`) against
NumPy's IEEE doubles, bit for bit: on int64 patterns (`add`, `mul`) and
on their uint32 halves (`add_halves`, `mul_halves`, the allocation-scan
kernel's arithmetic, run here as plain jnp)."""

import numpy as np
import pytest

import jax

from repro.pipeline import exact64 as x64

_SPECIAL = np.array(
    [
        0.0, 5e-324, 2.2250738585072009e-308, 2.2250738585072014e-308,
        1.0, 1.0 + 2.0**-52, 1.5, 2.0, 3.0, 0.1, 0.2, 0.3, 8.0, 1e-3,
        1e30, 1.7976931348623157e308, 2.0**-1074 * 3, 2.0**52, 2.0**53,
        2.0**53 + 2.0, 0.5 - 2.0**-54,
    ]
)


def _operands(kind: str, n: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    if kind == "bits":  # every binade, subnormals included
        bits = rng.integers(0, x64.INF, size=n, dtype=np.int64)
        return bits.view(np.float64)
    if kind == "times":  # the scheduler's magnitudes
        return np.round(rng.random(n) * 10.0 ** rng.integers(-3, 6, n), 6)
    if kind == "near":  # operands a few ulps apart: carries, ties
        base = rng.random(n) * 100.0
        return np.nextafter(base, np.inf) * (1 + rng.integers(0, 2, n))
    return np.resize(_SPECIAL, n)


def _run(fn, a, b):
    with jax.enable_x64():
        return x64.from_bits(jax.jit(fn)(x64.to_bits(a), x64.to_bits(b)))


def _run_halves(fn, a, b):
    """``fn`` on uint32 halves, split and joined outside the jitted call
    (which runs without x64, as the kernel's arithmetic does)."""
    with jax.enable_x64():
        a, b = (tuple(np.asarray(w) for w in x64.split(x64.to_bits(v)))
                for v in (a, b))
    out = jax.jit(fn)(a, b)
    assert all(w.dtype == np.uint32 for w in out)
    with jax.enable_x64():
        return x64.from_bits(x64.join(out))


@pytest.mark.parametrize("op", ["add", "mul", "add_halves", "mul_halves"])
@pytest.mark.parametrize("kind", ["bits", "times", "near", "special"])
def test_matches_numpy_bit_for_bit(op, kind):
    n = 4096
    a = _operands(kind, n, seed=1)
    b = _operands(kind, n, seed=2)
    if kind == "special":
        a, b = np.meshgrid(_SPECIAL, _SPECIAL)
        a, b = a.ravel(), b.ravel()
    with np.errstate(over="ignore"):
        want = a + b if op.startswith("add") else a * b
    run = _run_halves if op.endswith("_halves") else _run
    got = run(getattr(x64, op), a, b)
    bad = np.flatnonzero(got.view(np.int64) != want.view(np.int64))
    assert bad.size == 0, (op, kind, a[bad[:3]], b[bad[:3]], got[bad[:3]])


def test_add_infinity_and_order():
    inf = np.full(3, np.inf)
    fin = np.array([0.0, 1.5, 1e300])
    assert np.all(_run(x64.add, inf, fin) == np.inf)
    assert np.all(_run(x64.add, fin, inf) == np.inf)
    # Patterns of non-negative doubles sort as the values do; -inf's
    # pattern sorts below all of them.
    vals = np.sort(np.concatenate([_SPECIAL, [np.inf]]))
    bits = x64.to_bits(vals)
    assert np.all(np.diff(bits) >= 0)
    assert x64.NEG_INF < bits.min()
    assert x64.to_bits(-0.0) == 0

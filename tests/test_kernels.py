"""Per-kernel validation: shape/dtype sweeps vs the pure-jnp oracles.

All Pallas kernels run in interpret mode on CPU (TPU is the compile
target); every test asserts allclose against ref.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.lp_terms import (
    lp_terms,
    lp_terms_batch,
    lp_terms_batch_ref,
    lp_terms_ref,
)
from repro.kernels.port_stats import port_stats, port_stats_ref
from repro.kernels.quant import (
    dequantize_flat,
    dequantize_ref,
    quantize_flat,
    quantize_ref,
)
from repro.kernels.quant.kernel import dequantize_pallas, quantize_pallas


# ---------------------------------------------------------------- port_stats
@pytest.mark.parametrize(
    "M,N", [(1, 4), (5, 10), (16, 32), (7, 150), (100, 10)]
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_port_stats_sweep(M, N, dtype):
    rng = np.random.default_rng(M * 131 + N)
    d = np.where(
        rng.random((M, N, N)) < 0.4, rng.uniform(0.5, 9.0, (M, N, N)), 0.0
    )
    d = jnp.asarray(d, dtype)
    rho_k, tau_k = port_stats(d)
    rho_r, tau_r = port_stats_ref(d)
    np.testing.assert_allclose(rho_k, rho_r, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(tau_k), np.asarray(tau_r))


def test_port_stats_matches_numpy_host():
    """Kernel agrees with the host-side numpy implementation used by the
    scheduler control plane."""
    from repro.core.coflow import port_stats as np_port_stats

    rng = np.random.default_rng(3)
    d = np.where(rng.random((9, 13, 13)) < 0.5, rng.uniform(1, 5, (9, 13, 13)), 0.0)
    rho_k, tau_k = port_stats(jnp.asarray(d, jnp.float32))
    rho_n, tau_n = np_port_stats(d)
    np.testing.assert_allclose(np.asarray(rho_k), rho_n, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(tau_k), tau_n)


# ------------------------------------------------------------------ lp_terms
@pytest.mark.parametrize("M,P", [(10, 8), (100, 20), (130, 44), (256, 300)])
def test_lp_terms_sweep(M, P):
    rng = np.random.default_rng(M + P)
    Y = np.triu(rng.random((M, M)), 1)
    X = Y + np.tril(1 - Y.T, -1) + np.eye(M)
    p_rho = rng.uniform(0, 50, (M, P)).astype(np.float32)
    p_tau = rng.integers(0, 10, (M, P)).astype(np.float32)
    args = (jnp.asarray(X, jnp.float32), jnp.asarray(p_rho), jnp.asarray(p_tau))
    tl_k, tr_k = lp_terms(*args, 1 / 60.0, 8 / 3.0)
    tl_r, tr_r = lp_terms_ref(*args, 1 / 60.0, 8 / 3.0)
    np.testing.assert_allclose(tl_k, tl_r, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(tr_k, tr_r, rtol=1e-4, atol=1e-4)


def test_lp_terms_zero_delta():
    """EPS mode: delta_over_K = 0 zeroes the reconfiguration term."""
    rng = np.random.default_rng(0)
    M, P = 16, 8
    X = np.eye(M)
    p = jnp.asarray(rng.uniform(0, 5, (M, P)), jnp.float32)
    _, tr = lp_terms(jnp.asarray(X, jnp.float32), p, p, 1.0, 0.0)
    np.testing.assert_allclose(tr, 0.0)


# ------------------------------------------------------------ lp_terms batch
@pytest.mark.parametrize("B,M,P", [(1, 10, 8), (3, 20, 24), (4, 100, 20)])
def test_lp_terms_batch_sweep(B, M, P):
    """Batched kernel vs batched oracle vs per-instance oracle, with
    per-instance scales."""
    rng = np.random.default_rng(B * 1000 + M + P)
    Y = np.triu(rng.random((B, M, M)), 1)
    X = Y + np.tril(1 - np.swapaxes(Y, 1, 2), -1) + np.eye(M)
    p_rho = rng.uniform(0, 50, (B, M, P)).astype(np.float32)
    p_tau = rng.integers(0, 10, (B, M, P)).astype(np.float32)
    inv_R = rng.uniform(0.01, 0.1, B).astype(np.float32)
    dok = rng.uniform(0.0, 3.0, B).astype(np.float32)
    args = (
        jnp.asarray(X, jnp.float32),
        jnp.asarray(p_rho),
        jnp.asarray(p_tau),
        jnp.asarray(inv_R),
        jnp.asarray(dok),
    )
    tl_k, tr_k = lp_terms_batch(*args)
    tl_r, tr_r = lp_terms_batch_ref(*args)
    assert tl_k.shape == (B, M) and tr_k.shape == (B, M)
    np.testing.assert_allclose(tl_k, tl_r, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(tr_k, tr_r, rtol=1e-4, atol=1e-4)
    for b in range(B):
        tl_s, tr_s = lp_terms_ref(
            jnp.asarray(X[b], jnp.float32),
            jnp.asarray(p_rho[b]),
            jnp.asarray(p_tau[b]),
            float(inv_R[b]),
            float(dok[b]),
        )
        np.testing.assert_allclose(tl_k[b], tl_s, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(tr_k[b], tr_s, rtol=1e-4, atol=1e-4)


def test_lp_terms_batch_matches_lp_solver_shape():
    """The batched kernel consumes exactly the padded arrays the ensemble
    LP solver builds (zero-padded coflows/ports are harmless: nonnegative
    stats keep the row max on real columns)."""
    from repro.core.coflow import port_stats
    from repro.traffic.instances import random_instance

    ens = [
        random_instance(num_coflows=6, num_ports=4, seed=0),
        random_instance(num_coflows=9, num_ports=3, seed=1),
    ]
    Mp, Pp = 9, 8
    B = len(ens)
    X = np.zeros((B, Mp, Mp), np.float32)
    rho_p = np.zeros((B, Mp, Pp), np.float32)
    tau_p = np.zeros((B, Mp, Pp), np.float32)
    inv_R = np.zeros(B, np.float32)
    dok = np.zeros(B, np.float32)
    for b, inst in enumerate(ens):
        M, P = inst.num_coflows, 2 * inst.num_ports
        rho, tau = port_stats(inst.demands)
        rho_p[b, :M, :P] = rho
        tau_p[b, :M, :P] = tau
        X[b, :Mp, :Mp] = np.eye(Mp)
        inv_R[b] = 1.0 / inst.aggregate_rate
        dok[b] = inst.delta / inst.num_cores
    tl, tr = lp_terms_batch(
        jnp.asarray(X), jnp.asarray(rho_p), jnp.asarray(tau_p),
        jnp.asarray(inv_R), jnp.asarray(dok),
    )
    for b, inst in enumerate(ens):
        M = inst.num_coflows
        rho, tau = port_stats(inst.demands)
        np.testing.assert_allclose(
            np.asarray(tl[b, :M]), rho.max(axis=1) * inv_R[b], rtol=1e-5
        )
        np.testing.assert_allclose(
            np.asarray(tr[b, :M]), tau.max(axis=1) * dok[b], rtol=1e-5
        )


# --------------------------------------------------------------- flash attn
ATTN_CASES = [
    # B, Hq, Hkv, Sq, Skv, D, causal, window, off
    (2, 4, 2, 256, 256, 64, True, None, 0),
    (1, 8, 1, 128, 128, 64, True, None, 0),
    (1, 4, 4, 200, 200, 64, True, None, 0),      # non-multiple seq
    (1, 2, 2, 384, 384, 64, True, 128, 0),       # sliding window
    (1, 2, 2, 256, 256, 64, True, 100, 0),       # non-tile-aligned window
    (1, 2, 1, 8, 512, 64, True, None, 504),      # decode: 1 new block
    (1, 2, 2, 128, 128, 128, False, None, 0),    # bidirectional
    (1, 3, 1, 64, 320, 32, True, None, 256),     # offset mid-cache
]


@pytest.mark.parametrize("case", ATTN_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(case, dtype):
    B, Hq, Hkv, Sq, Skv, D, causal, window, off = case
    rng = np.random.default_rng(hash(case) % 2**31)
    q = jnp.asarray(rng.standard_normal((B, Hq, Sq, D)), dtype)
    k = jnp.asarray(rng.standard_normal((B, Hkv, Skv, D)), dtype)
    v = jnp.asarray(rng.standard_normal((B, Hkv, Skv, D)), dtype)
    o_k = flash_attention(q, k, v, causal, window, off)
    o_r = attention_ref(q, k, v, causal, window, off)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(o_k, np.float32), np.asarray(o_r, np.float32),
        rtol=tol, atol=tol,
    )


def test_flash_attention_grad_matches_ref():
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((1, 2, 64, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 1, 64, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 1, 64, 32)), jnp.float32)

    def loss_k(q, k, v):
        return (flash_attention(q, k, v) ** 2).sum()

    def loss_r(q, k, v):
        return (attention_ref(q, k, v) ** 2).sum()

    g_k = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_k, g_r):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_flash_attention_softmax_rows_sum_to_one():
    """Sanity: output of attention over constant V equals that constant."""
    q = jnp.ones((1, 2, 64, 32), jnp.float32)
    k = jnp.asarray(
        np.random.default_rng(0).standard_normal((1, 1, 64, 32)), jnp.float32
    )
    v = jnp.full((1, 1, 64, 32), 3.5, jnp.float32)
    o = flash_attention(q, k, v)
    np.testing.assert_allclose(o, 3.5, rtol=1e-5)


# --------------------------------------------------------------------- quant
@pytest.mark.parametrize("R,C", [(4, 128), (64, 512), (33, 300), (1, 64)])
def test_quant_matches_ref(R, C):
    rng = np.random.default_rng(R * 7 + C)
    x = jnp.asarray(rng.standard_normal((R, C)) * 3.0, jnp.float32)
    noise = jnp.asarray(rng.random((R, C)), jnp.float32)
    q_k, s_k = quantize_pallas(x, noise)
    q_r, s_r = quantize_ref(x, noise)
    np.testing.assert_array_equal(np.asarray(q_k), np.asarray(q_r))
    np.testing.assert_allclose(s_k, s_r, rtol=1e-6)
    d_k = dequantize_pallas(q_k, s_k)
    d_r = dequantize_ref(q_r, s_r)
    np.testing.assert_allclose(d_k, d_r, rtol=1e-6)


def test_quant_roundtrip_error_bound():
    """|x - dq(q(x))| <= scale per element (1 ulp of the int8 grid)."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((16, 256)) * 5.0, jnp.float32)
    noise = jnp.asarray(rng.random((16, 256)), jnp.float32)
    q, s = quantize_pallas(x, noise)
    d = dequantize_pallas(q, s)
    err = np.abs(np.asarray(d) - np.asarray(x))
    assert np.all(err <= np.asarray(s)[:, None] + 1e-6)


def test_quant_stochastic_rounding_unbiased():
    """E[dq(q(x))] ~= x under stochastic rounding."""
    x = jnp.full((1, 512), 0.3, jnp.float32)  # 0.3/scale is fractional
    key = jax.random.PRNGKey(0)
    acc = np.zeros((1, 512))
    trials = 64
    for i in range(trials):
        noise = jax.random.uniform(jax.random.fold_in(key, i), (1, 512))
        q, s = quantize_pallas(x, noise)
        acc += np.asarray(dequantize_pallas(q, s))
    mean = acc / trials
    np.testing.assert_allclose(mean.mean(), 0.3, rtol=0.05)


def test_quantize_flat_roundtrip():
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal(1000), jnp.float32)
    q, s, n = quantize_flat(x, jax.random.PRNGKey(1))
    out = dequantize_flat(q, s, n)
    assert out.shape == (1000,)
    err = np.abs(np.asarray(out) - np.asarray(x))
    assert err.max() < 0.1  # |x| ~ 3 max -> scale ~ 0.03


# ---------------------------------------------------------------- event_resolve
def _random_event_state(seed, G, F, N):
    rng = np.random.default_rng(seed)
    return dict(
        src=jnp.asarray(rng.integers(0, N, (G, F)), jnp.int32),
        dst=jnp.asarray(rng.integers(0, N, (G, F)), jnp.int32),
        rel=jnp.asarray(rng.uniform(0, 10, (G, F)), jnp.float32),
        free_in=jnp.asarray(rng.uniform(0, 10, (G, N)), jnp.float32),
        free_out=jnp.asarray(rng.uniform(0, 10, (G, N)), jnp.float32),
        pending=jnp.asarray(rng.random((G, F)) < 0.7),
        t=jnp.asarray(rng.uniform(0, 10, G), jnp.float32),
    )


@pytest.mark.parametrize("G,F,N", [(1, 1, 1), (3, 17, 5), (8, 130, 9)])
def test_event_resolve_kernel_matches_ref(G, F, N):
    """Pallas idle/first-waiting reduction == jnp oracle across padding."""
    from repro.kernels.event_resolve import event_resolve

    s = _random_event_state(G * 1000 + F, G, F, N)
    got = np.asarray(event_resolve(**s, use_kernel=True, interpret=True))
    ref = np.asarray(event_resolve(**s, use_kernel=False))
    assert got.dtype == ref.dtype == np.bool_
    assert np.array_equal(got, ref)


def test_event_resolve_matches_numpy_primitive():
    """Both paths reproduce core.circuit.resolve_event member by member."""
    from repro.core.circuit import resolve_event
    from repro.kernels.event_resolve import event_resolve

    s = _random_event_state(7, 4, 23, 6)
    got = np.asarray(event_resolve(**s, use_kernel=True, interpret=True))
    for g in range(4):
        waiting = np.asarray(s["pending"][g]) & (
            np.asarray(s["rel"][g]) <= float(s["t"][g])
        )
        ref = resolve_event(
            np.asarray(s["src"][g], dtype=np.int64),
            np.asarray(s["dst"][g], dtype=np.int64),
            np.asarray(s["free_in"][g]),
            np.asarray(s["free_out"][g]),
            waiting,
            float(s["t"][g]),
        )
        assert np.array_equal(got[g], ref), g


def test_event_resolve_reserving_semantics():
    """A waiting-but-blocked flow reserves its ports: the start mask must
    exclude lower-priority flows sharing them even when idle."""
    from repro.kernels.event_resolve import event_resolve

    # All three flows idle at t=0.  flow0 (0->1) is first on both its
    # ports and starts; flow1 (2->1) loses egress 1 to flow0's claim;
    # flow2 (2->3) is idle but flow1 reserves ingress 2 ahead of it, so
    # it must not start either (the reserving property).
    src = jnp.asarray([[0, 2, 2]], jnp.int32)
    dst = jnp.asarray([[1, 1, 3]], jnp.int32)
    rel = jnp.zeros((1, 3), jnp.float32)
    free_in = jnp.zeros((1, 4), jnp.float32)
    free_out = jnp.zeros((1, 4), jnp.float32)
    pending = jnp.ones((1, 3), bool)
    t = jnp.zeros((1,), jnp.float32)
    for use_kernel in (True, False):
        got = np.asarray(
            event_resolve(
                src, dst, rel, free_in, free_out, pending, t,
                use_kernel=use_kernel, interpret=True,
            )
        )
        assert got.tolist() == [[True, False, False]]


# ---------------------------------------------------------------- pair_resolve
@pytest.mark.parametrize("G,N", [(1, 1), (2, 5), (6, 9), (3, 16)])
def test_pair_resolve_kernel_matches_ref(G, N):
    """Pallas pair-space round reduction == jnp oracle across padding."""
    from repro.kernels.event_resolve import pair_resolve, pair_resolve_ref

    rng = np.random.default_rng(G * 100 + N)
    F = 40
    ids = rng.integers(0, F, (G, N, N)).astype(np.float64)
    claim = jnp.asarray(
        np.where(rng.random((G, N, N)) < 0.6, ids, float(F)), jnp.float32
    )
    idle = jnp.asarray(rng.random((G, N, N)) < 0.5)
    got = np.asarray(
        pair_resolve(claim, idle, use_kernel=True, interpret=True)
    )
    ref = np.asarray(pair_resolve_ref(claim, idle))
    assert got.dtype == ref.dtype == np.bool_
    assert np.array_equal(got, ref)


@pytest.mark.parametrize("discipline", ["reserving", "greedy"])
def test_pair_resolve_f64_separation_parity(discipline):
    """The f64-safety contract of the kernel engine: all f64 comparisons
    (rel <= t, free <= t) happen outside the kernel, which only reduces
    exact integer flow ids — so the pair round through `pair_heads` +
    `pair_resolve` (kernel and oracle) must match the flow-space
    `resolve_event` f64 reference bit for bit."""
    from repro.core.circuit import pair_heads, resolve_event
    from repro.kernels.event_resolve import pair_resolve

    for seed in range(25):
        rng = np.random.default_rng(seed)
        F, N = int(rng.integers(1, 40)), int(rng.integers(1, 8))
        src = rng.integers(0, N, F)
        dst = rng.integers(0, N, F)
        # f64 times with sub-ulp-of-f32 structure: parity must not depend
        # on any f32 rounding of the time comparisons.
        free_in = rng.uniform(0, 10, N) * (1 + 1e-12)
        free_out = rng.uniform(0, 10, N) * (1 + 1e-12)
        waiting = rng.random(F) < 0.7
        t = float(rng.uniform(0, 10))

        ref = resolve_event(
            src, dst, free_in, free_out, waiting, t, discipline=discipline
        )
        heads = pair_heads(src, dst, waiting, N)
        has = heads < F
        idle = has & (free_in[:, None] <= t) & (free_out[None, :] <= t)
        claiming = has if discipline == "reserving" else idle
        claim = jnp.asarray(
            np.where(claiming, heads, F)[None].astype(np.float64),
            jnp.float32,
        )
        for use_kernel in (True, False):
            sp = np.asarray(
                pair_resolve(
                    claim, jnp.asarray(idle[None]), use_kernel, interpret=True
                )
            )[0]
            got = sp[src, dst] & (heads[src, dst] == np.arange(F))
            assert np.array_equal(got, ref), (seed, use_kernel)


def test_resolve_event_pairs_matches_flow_space():
    """NumPy pair-space primitive == flow-space resolve_event (the
    reduction the wide and kernel calendars both rely on)."""
    from repro.core.circuit import (
        pair_heads,
        resolve_event,
        resolve_event_pairs,
    )

    for seed in range(20):
        rng = np.random.default_rng(1000 + seed)
        F, N = int(rng.integers(1, 30)), int(rng.integers(1, 7))
        src = rng.integers(0, N, F)
        dst = rng.integers(0, N, F)
        free_in = rng.uniform(0, 5, N)
        free_out = rng.uniform(0, 5, N)
        waiting = rng.random(F) < 0.6
        t = float(rng.uniform(0, 5))
        for discipline in ("reserving", "greedy"):
            heads = pair_heads(src, dst, waiting, N)
            has = heads < F
            idle = has & (free_in[:, None] <= t) & (free_out[None, :] <= t)
            claiming = has if discipline == "reserving" else idle
            sp = resolve_event_pairs(np.where(claiming, heads, F), idle)
            got = sp[src, dst] & (heads[src, dst] == np.arange(F))
            ref = resolve_event(
                src, dst, free_in, free_out, waiting, t,
                discipline=discipline,
            )
            assert np.array_equal(got, ref), (seed, discipline)


def test_event_resolve_validation_names_operand():
    """The ops wrappers reject malformed operands up front with a typed
    error naming the offending argument (not an XLA shape error later)."""
    from repro.kernels.event_resolve import (
        EventResolveArgumentError,
        event_resolve,
        pair_resolve,
    )

    s = _random_event_state(0, 2, 5, 3)
    with pytest.raises(EventResolveArgumentError, match="src"):
        event_resolve(**{**s, "src": s["src"].astype(jnp.float32)})
    with pytest.raises(EventResolveArgumentError, match="pending"):
        event_resolve(**{**s, "pending": s["pending"].astype(jnp.int32)})
    with pytest.raises(EventResolveArgumentError, match="free_out"):
        event_resolve(**{**s, "free_out": s["free_out"][:, :2]})
    with pytest.raises(EventResolveArgumentError, match="t"):
        event_resolve(**{**s, "t": s["t"][:1]})
    with pytest.raises(EventResolveArgumentError, match="rel"):
        event_resolve(**{**s, "rel": np.asarray(s["rel"])[0]})

    claim = jnp.zeros((2, 3, 3), jnp.float32)
    idle = jnp.zeros((2, 3, 3), bool)
    with pytest.raises(EventResolveArgumentError, match="claim"):
        pair_resolve(claim.astype(jnp.int32), idle)
    with pytest.raises(EventResolveArgumentError, match="idle"):
        pair_resolve(claim, idle[:, :2])
    with pytest.raises(EventResolveArgumentError, match="claim"):
        pair_resolve(jnp.zeros((2, 3, 4), jnp.float32), idle)

"""Ordering LP relaxation for K-core OCS coflow scheduling (paper Sec. IV-A2).

Variables: completion times T_m and pairwise precedence x_{m,m'} in [0,1]
with x_{m,m'} + x_{m',m} = 1.  Constraints per coflow m and port p:

  transmission (Eq. 4):     T_m >= (1/R) ( rho_{m,p} + sum_{m'!=m} rho_{m',p} x_{m',m} )
  reconfiguration (Eq. 5):  T_m >= (delta/K) ( tau_{m,p} + sum_{m'!=m} tau_{m',p} x_{m',m} )
  release (Eq. 6):          T_m >= a_m

Objective: min sum_m w_m T_m.  The optimum lower-bounds the optimal weighted
CCT of the original problem, and the optimal T~_m define the global order.

Three solvers:
  * solve_exact       — scipy/HiGHS on the reduced LP (x_{m',m} = 1 - x_{m,m'}
                        for m < m' eliminated); exact, used for certificates.
  * solve_subgradient_batch — ensemble solver: pads a batch of instances to a
                        shared bucket shape and runs the projected-subgradient
                        iteration vectorized over the leading ensemble axis
                        (padded coflows/ports masked out of the max terms and
                        the objective).  The per-step (B, Mp, Mp) @ (B, Mp, Pp)
                        contractions are the `lp_terms_batch` kernel's shape.
  * solve_subgradient — the batched solver on one instance.  Both run
                        projected subgradient on the equivalent convex
                        piecewise-linear program
                            min_Y  F(Y) = sum_m w_m T_m(Y),
                            T_m(Y) = max(a_m, max_p (X~^T P_rho)[m,p] / R,
                                              max_p (delta/K)(X~^T P_tau)[m,p])
                        where X~ has diag 1, X~[a,b] = Y[a,b] (a<b),
                        1 - Y[b,a] (a>b), and Y is box-projected to [0,1].
                        For fixed precedences the optimal T is the pointwise
                        max of the RHS, so this is the same LP.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Sequence

import numpy as np
import scipy.sparse as sp
from scipy.optimize import linprog

import jax
import jax.numpy as jnp

from repro.core.coflow import CoflowInstance, port_stats
from repro.trace import to_host

__all__ = [
    "LPSolution",
    "LPSolutionBatch",
    "solve_exact",
    "solve_subgradient",
    "solve_subgradient_batch",
    "solve_subgradient_batch_arrays",
    "pack_lp_arrays",
    "lp_objective",
]


@dataclasses.dataclass(frozen=True)
class LPSolution:
    """Solution of the ordering LP relaxation."""

    completion: np.ndarray  # (M,) T~_m
    precedence: np.ndarray  # (M, M) x_{m,m'}; diag = 0 by convention
    objective: float  # sum_m w_m T~_m
    method: str
    iterations: int = 0

    def order(self) -> np.ndarray:
        """Coflow ids sorted by non-decreasing T~_m (Algorithm 1 Line 2)."""
        return np.argsort(self.completion, kind="stable")


def _pair_index(m: int):
    """Map (a, b), a < b -> flat pair id; returns (ia, ib, P)."""
    ia, ib = np.triu_indices(m, k=1)
    return ia, ib, ia.shape[0]


def lp_objective(instance: CoflowInstance, completion: np.ndarray) -> float:
    return float(np.dot(instance.weights, completion))


# ---------------------------------------------------------------------------
# Exact solver (HiGHS)
# ---------------------------------------------------------------------------


def solve_exact(instance: CoflowInstance) -> LPSolution:
    """Solve the ordering LP exactly with scipy's HiGHS backend.

    Reduced variables: z = [T_1..T_M, y_1..y_P] with y_{(a,b)} = x_{a,b} for
    a < b (so x_{b,a} = 1 - y_{(a,b)}).  Constraint rows (<= form):

      -T_m + (1/R) [ sum_{m'<m} rho_{m',p} y_{(m',m)}
                     - sum_{m'>m} rho_{m',p} y_{(m,m')} ]
          <= -(1/R) [ rho_{m,p} + sum_{m'>m} rho_{m',p} ]

    and the analogous tau rows with delta/K.  Release handled via bounds.
    """
    M, N = instance.num_coflows, instance.num_ports
    K = instance.num_cores
    R = instance.aggregate_rate
    delta = instance.delta
    rho, tau = port_stats(instance.demands)
    tau = tau.astype(np.float64)
    ia, ib, P = _pair_index(M)

    rows, cols, vals = [], [], []
    rhs = []
    row_id = 0

    def add_block(stats: np.ndarray, coef: float):
        """Append M*2N constraint rows for one capacity family."""
        nonlocal row_id
        if coef == 0.0:
            return
        # For each coflow m and port p one row.
        for m in range(M):
            # y columns: pairs (m', m) with m' < m get +coef*stats[m',p];
            # pairs (m, m') with m' > m get -coef*stats[m',p].
            lower = np.arange(0, m)  # m' < m
            upper = np.arange(m + 1, M)  # m' > m
            # pair id for (a,b): index into triu list. Build lookup lazily.
            for p in range(2 * N):
                r = row_id
                row_id += 1
                rows.append(r)
                cols.append(p_T(m))
                vals.append(-1.0)
                base = stats[m, p] + stats[upper, p].sum() if upper.size else stats[m, p]
                rhs.append(-coef * base)
                if lower.size:
                    pid = pair_id[lower, m]
                    nz = stats[lower, p] != 0
                    if nz.any():
                        rows.extend([r] * int(nz.sum()))
                        cols.extend((M + pid[nz]).tolist())
                        vals.extend((coef * stats[lower[nz], p]).tolist())
                if upper.size:
                    pid = pair_id[m, upper]
                    nz = stats[upper, p] != 0
                    if nz.any():
                        rows.extend([r] * int(nz.sum()))
                        cols.extend((M + pid[nz]).tolist())
                        vals.extend((-coef * stats[upper[nz], p]).tolist())

    def p_T(m: int) -> int:
        return m

    # Dense pair-id lookup (M, M) for the strict upper triangle.
    pair_id = np.full((M, M), -1, dtype=np.int64)
    pair_id[ia, ib] = np.arange(P)

    add_block(rho, 1.0 / R)
    if delta > 0:
        add_block(tau, delta / K)

    n_var = M + P
    A = sp.csr_matrix(
        (np.asarray(vals), (np.asarray(rows), np.asarray(cols))),
        shape=(row_id, n_var),
    )
    c = np.concatenate([instance.weights, np.zeros(P)])
    bounds = [(float(a), None) for a in instance.releases] + [(0.0, 1.0)] * P
    res = linprog(
        c,
        A_ub=A,
        b_ub=np.asarray(rhs),
        bounds=bounds,
        method="highs",
    )
    if not res.success:  # pragma: no cover - HiGHS is robust on these LPs
        raise RuntimeError(f"ordering LP failed: {res.message}")
    T = res.x[:M]
    y = res.x[M:]
    x = np.zeros((M, M))
    x[ia, ib] = y
    x[ib, ia] = 1.0 - y
    return LPSolution(
        completion=T,
        precedence=x,
        objective=float(res.fun),
        method="exact",
        iterations=int(res.nit) if res.nit is not None else 0,
    )


# ---------------------------------------------------------------------------
# JAX projected-subgradient solver
# ---------------------------------------------------------------------------


def warm_start_Y0_dense(
    weights: np.ndarray, glb: np.ndarray, warm_start_order: np.ndarray | None = None
) -> np.ndarray:
    """Strict-upper-triangular warm start from per-coflow arrays.

    Array-in flavor of the default warm start (the weighted global
    lower-bound order, WSPT-like) — the streaming service builds epoch
    warm starts from its resident per-slot vectors without materializing
    a `CoflowInstance`.  Y0[a, b] = 1 iff a precedes b, kept for a < b.
    """
    M = int(np.asarray(weights).shape[0])
    if warm_start_order is None:
        score = np.asarray(weights) / np.maximum(np.asarray(glb), 1e-12)
        warm_start_order = np.argsort(-score, kind="stable")
    pos = np.empty(M, dtype=np.int64)
    pos[warm_start_order] = np.arange(M)
    Y0 = (pos[:, None] < pos[None, :]).astype(np.float32)  # x_ab=1 iff a first
    return np.triu(Y0, k=1)


def _warm_start_Y0(
    instance: CoflowInstance, warm_start_order: np.ndarray | None
) -> np.ndarray:
    """Strict-upper-triangular warm start from a priority order.

    Defaults to the weighted global lower-bound order (WSPT-like);
    Y0[a, b] = 1 iff a precedes b, kept only for a < b.
    """
    return warm_start_Y0_dense(
        instance.weights, instance.global_lower_bound(), warm_start_order
    )


def _precedence_from_Y(Y: np.ndarray) -> np.ndarray:
    """Full precedence matrix (diag 0, x_ab + x_ba = 1) from the solver's
    strict-upper-triangular Y."""
    M = Y.shape[0]
    x = np.zeros((M, M))
    iu = np.triu_indices(M, k=1)
    x[iu] = Y[iu]
    x[(iu[1], iu[0])] = 1.0 - Y[iu]
    return x


def solve_subgradient(
    instance: CoflowInstance,
    iters: int = 3000,
    warm_start_order: np.ndarray | None = None,
) -> LPSolution:
    """Projected-subgradient solve of the ordering LP (JAX, jit).

    Returns a *feasible* (Y in box, pair equalities by construction) solution;
    its objective upper-bounds the LP optimum but in practice lands within
    ~1% of HiGHS (see tests/test_lp.py), and the induced order matches the
    exact order's weighted CCT.  This is the batched solver on a one-member
    ensemble at the instance's exact shape, so it agrees bit for bit with
    `solve_subgradient_batch` on any exact-shape bucket holding the instance.
    """
    (sol,) = solve_subgradient_batch(
        [instance], iters=iters, warm_start_orders=[warm_start_order]
    )
    return dataclasses.replace(sol, method="subgradient")


# ---------------------------------------------------------------------------
# Batched (ensemble) JAX solver
# ---------------------------------------------------------------------------


def _completion_from_Y_masked(
    Y: jnp.ndarray,
    p_rho: jnp.ndarray,
    p_tau: jnp.ndarray,
    releases: jnp.ndarray,
    inv_R: jnp.ndarray,
    delta_over_K: jnp.ndarray,
    coflow_mask: jnp.ndarray,
    port_mask: jnp.ndarray,
    temp: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Shape-padded T_m(Y) for one ensemble member (vmapped over B).

    With ``temp`` the hard max over constraint rows is replaced by a
    temperature-scaled logsumexp (a smooth upper bound), which gives the
    annealed-smoothing solver useful gradients on plateaus.  Padded coflow
    rows/columns of X are zeroed (their T comes out exactly 0 and their
    weight is 0), and padded port columns are masked to -inf so they
    contribute neither to the hard max nor to the smoothed logsumexp.
    """
    M = Y.shape[0]
    iu = jnp.triu(jnp.ones((M, M), dtype=bool), k=1)
    il = jnp.tril(jnp.ones((M, M), dtype=bool), k=-1)
    X = jnp.where(iu, Y, 0.0) + jnp.where(il, 1.0 - Y.T, 0.0)
    X = X + jnp.eye(M, dtype=Y.dtype)
    X = X * (coflow_mask[:, None] * coflow_mask[None, :])
    load = (X.T @ p_rho) * inv_R  # (Mp, Pp) — lp_terms_batch's contraction
    rec = (X.T @ p_tau) * delta_over_K
    stacked = jnp.concatenate([load, rec, releases[:, None]], axis=1)
    col_mask = jnp.concatenate(
        [port_mask, port_mask, jnp.ones((1,), dtype=bool)]
    )
    neg = jnp.asarray(-jnp.inf, stacked.dtype)
    if temp is None:
        return jnp.where(col_mask, stacked, neg).max(axis=1)
    z = jnp.where(col_mask, stacked / temp, neg)
    return temp * jax.scipy.special.logsumexp(z, axis=1)


@functools.partial(jax.jit, static_argnames=("iters", "lr"))
def _subgradient_run_batch(
    Y0: jnp.ndarray,  # (B, Mp, Mp)
    p_rho: jnp.ndarray,  # (B, Mp, Pp)
    p_tau: jnp.ndarray,  # (B, Mp, Pp)
    weights: jnp.ndarray,  # (B, Mp), 0 on padded coflows
    releases: jnp.ndarray,  # (B, Mp)
    inv_R: jnp.ndarray,  # (B,)
    delta_over_K: jnp.ndarray,  # (B,)
    coflow_mask: jnp.ndarray,  # (B, Mp) bool
    port_mask: jnp.ndarray,  # (B, Pp) bool
    *,
    iters: int,
    lr: float = 0.05,
):
    """Ensemble projected Adam: the whole batch advances in lockstep.

    Instances are independent, so the gradient of the *summed* smooth
    objective is exactly the stack of per-instance gradients; Adam is
    elementwise, so each member follows its own trajectory.  The smoothing
    temperature decays geometrically from ~scale of the objective spread
    to ~0; per-instance best-so-far is tracked under the true
    piecewise-linear objective, so the returned point is never worse than
    the warm start.
    """

    comp_hard = jax.vmap(
        lambda Y, r, t, rel, ir, dk, cm, pm: _completion_from_Y_masked(
            Y, r, t, rel, ir, dk, cm, pm
        )
    )
    comp_smooth = jax.vmap(
        lambda Y, r, t, rel, ir, dk, cm, pm, tp: _completion_from_Y_masked(
            Y, r, t, rel, ir, dk, cm, pm, temp=tp
        )
    )

    def true_objective(Y):  # (B,)
        T = comp_hard(
            Y, p_rho, p_tau, releases, inv_R, delta_over_K,
            coflow_mask, port_mask,
        )
        return jnp.sum(weights * T, axis=1)

    def smooth_total(Y, temps):  # scalar — sum over the ensemble
        T = comp_smooth(
            Y, p_rho, p_tau, releases, inv_R, delta_over_K,
            coflow_mask, port_mask, temps,
        )
        return jnp.sum(weights * T)

    grad_fn = jax.grad(smooth_total)
    T0 = comp_hard(
        Y0, p_rho, p_tau, releases, inv_R, delta_over_K,
        coflow_mask, port_mask,
    )
    temp0 = jnp.maximum(jnp.max(T0, axis=1) * 0.05, 1e-3)  # (B,)

    def step(carry, t):
        Y, m, v, best_Y, best_F = carry
        temps = temp0 * jnp.exp(-4.0 * t / iters) + 1e-3
        g = grad_fn(Y, temps)
        m = 0.9 * m + 0.1 * g
        v = 0.999 * v + 0.001 * g * g
        mh = m / (1.0 - 0.9 ** (t + 1.0))
        vh = v / (1.0 - 0.999 ** (t + 1.0))
        Y = jnp.clip(Y - lr * mh / (jnp.sqrt(vh) + 1e-8), 0.0, 1.0)
        F = true_objective(Y)
        better = F < best_F
        return (
            Y,
            m,
            v,
            jnp.where(better[:, None, None], Y, best_Y),
            jnp.where(better, F, best_F),
        ), F

    init = (Y0, jnp.zeros_like(Y0), jnp.zeros_like(Y0), Y0, true_objective(Y0))
    (_, _, _, best_Y, best_F), hist = jax.lax.scan(
        step, init, jnp.arange(iters, dtype=jnp.float32)
    )
    T_best = comp_hard(
        best_Y, p_rho, p_tau, releases, inv_R, delta_over_K,
        coflow_mask, port_mask,
    )
    return best_Y, T_best, best_F, hist


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class LPSolutionBatch:
    """Padded ensemble solution of the ordering LP — the array-form result.

    One row per bucket member, padded to the bucket shape; padded coflow
    slots carry completion 0 and contribute nothing.  The arrays may be
    **device-resident** (and sharded across the ensemble axis) exactly as
    the batched solver produced them; `repro.experiments.results.
    device_gather` is the aggregation step that brings a batch to host
    numpy.  `order_batch` turns the padded completions into every
    member's global order in one masked stable argsort (the same sort
    `LPOrder.order_batch` applies when `Pipeline.run_batch` re-pads
    per-instance solutions); per-instance `LPSolution`s are materialized
    only on demand via `unpack`.
    """

    completion: Any  # (B, Mp) T~_m, 0 on padded slots
    y: Any  # (B, Mp, Mp) strict-upper-tri precedence values
    objective: Any  # (B,) sum_m w_m T~_m
    method: str = dataclasses.field(metadata=dict(static=True))
    iterations: int = dataclasses.field(
        default=0, metadata=dict(static=True)
    )

    @property
    def num_members(self) -> int:
        return int(self.completion.shape[0])

    def order_batch(self, coflow_mask: np.ndarray) -> np.ndarray:
        """(B, Mp) padded orders: non-decreasing T~_m per member, padded
        slots pushed stably to the tail (Algorithm 1 Line 2, whole bucket).

        Row ``b`` restricted to its first M_b entries is bit-identical to
        ``LPSolution.order()`` of that member alone: masking padded slots
        to +inf before a stable argsort leaves the relative order of the
        real entries untouched.
        """
        comp = to_host(self.completion).astype(np.float64)
        key = np.where(np.asarray(coflow_mask), comp, np.inf)
        return np.argsort(key, axis=1, kind="stable")

    def unpack(self, num_coflows: Sequence[int]) -> list[LPSolution]:
        """Materialize per-instance `LPSolution`s (host side, on demand).

        Gathers device (possibly sharded) arrays to host numpy first; the
        f64 conversion matches the legacy list-of-`LPSolution` path."""
        comp, y, obj = (
            a.astype(np.float64)
            for a in to_host(self.completion, self.y, self.objective)
        )
        out = []
        for b, M in enumerate(num_coflows):
            out.append(
                LPSolution(
                    completion=comp[b, :M],
                    precedence=_precedence_from_Y(y[b, :M, :M]),
                    objective=float(obj[b]),
                    method=self.method,
                    iterations=self.iterations,
                )
            )
        return out


def pack_lp_arrays(
    instances: Sequence[CoflowInstance],
    pad_coflows: int | None = None,
    pad_ports: int | None = None,
    warm_start_orders: Sequence[np.ndarray | None] | None = None,
    pad_members: int | None = None,
) -> dict[str, np.ndarray]:
    """Pad an ensemble into the batched LP solver's input arrays.

    This is the **single** host-side padding step of the LP phase: the
    returned dict feeds `solve_subgradient_batch_arrays` as-is (and is what
    `repro.pipeline.ensemble_batch.EnsembleBatch` embeds, so LP, ordering,
    allocation and circuit all read one padded representation).
    ``pad_members`` rounds the member axis up (for sharding to a device
    count); padded members are all-masked zero rows — exact no-ops.
    """
    instances = list(instances)
    B = len(instances)
    if warm_start_orders is None:
        warm_start_orders = [None] * B
    Ms = [inst.num_coflows for inst in instances]
    Ps = [2 * inst.num_ports for inst in instances]
    Mp = pad_coflows if pad_coflows is not None else max(Ms, default=0)
    Pp = pad_ports if pad_ports is not None else max(Ps, default=0)
    if B and (Mp < max(Ms) or Pp < max(Ps)):
        raise ValueError(
            f"bucket shape ({Mp}, {Pp}) too small for ensemble maxima "
            f"({max(Ms)}, {max(Ps)})"
        )
    Bp = B if pad_members is None else max(pad_members, B)

    Y0 = np.zeros((Bp, Mp, Mp), dtype=np.float32)
    p_rho = np.zeros((Bp, Mp, Pp), dtype=np.float32)
    p_tau = np.zeros((Bp, Mp, Pp), dtype=np.float32)
    weights = np.zeros((Bp, Mp), dtype=np.float32)
    releases = np.zeros((Bp, Mp), dtype=np.float32)
    inv_R = np.zeros(Bp, dtype=np.float32)
    delta_over_K = np.zeros(Bp, dtype=np.float32)
    coflow_mask = np.zeros((Bp, Mp), dtype=bool)
    port_mask = np.zeros((Bp, Pp), dtype=bool)
    for b, inst in enumerate(instances):
        M, P = Ms[b], Ps[b]
        rho, tau = port_stats(inst.demands)
        p_rho[b, :M, :P] = rho
        p_tau[b, :M, :P] = tau
        weights[b, :M] = inst.weights
        releases[b, :M] = inst.releases
        inv_R[b] = 1.0 / inst.aggregate_rate
        delta_over_K[b] = inst.delta / inst.num_cores
        coflow_mask[b, :M] = True
        port_mask[b, :P] = True
        Y0[b, :M, :M] = _warm_start_Y0(inst, warm_start_orders[b])
    return dict(
        Y0=Y0, p_rho=p_rho, p_tau=p_tau, weights=weights, releases=releases,
        inv_R=inv_R, delta_over_K=delta_over_K, coflow_mask=coflow_mask,
        port_mask=port_mask,
    )


#: Least number of members a device's block of the batched solve holds
#: (see `solve_subgradient_batch_arrays`).
_MIN_MEMBERS_PER_DEVICE = 2


def padded_members(num_members: int, num_devices: int = 1) -> int:
    """Members the batched solve runs for ``num_members`` real ones over
    ``num_devices`` devices: at least `_MIN_MEMBERS_PER_DEVICE` a device,
    rounded up to the device count (see `solve_subgradient_batch_arrays`)."""
    least = max(num_members, _MIN_MEMBERS_PER_DEVICE * num_devices)
    return -(-least // num_devices) * num_devices


def _pad_members(x, Bp: int):
    """Pad the leading (member) axis of a host or device array with zeros."""
    pads = [(0, Bp - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pads) if isinstance(x, jax.Array) else np.pad(x, pads)


def solve_subgradient_batch_arrays(
    arrays,
    iters: int = 3000,
    sharding=None,
) -> LPSolutionBatch:
    """Array-in/array-out ensemble LP solve.

    ``arrays`` is the `pack_lp_arrays` dict (what
    `EnsembleBatch.lp_arrays()` returns).  ``sharding`` places every
    input with a `jax.sharding.Sharding` (typically a data-axis
    `NamedSharding`) before the jitted solve, so the subgradient iteration
    runs SPMD across the ensemble axis; members are independent
    (vmap-parallel), so sharded and unsharded runs are bit-identical per
    member.  Returns the padded `LPSolutionBatch` — nothing is unpadded
    here.
    """
    names = (
        "Y0", "p_rho", "p_tau", "weights", "releases", "inv_R",
        "delta_over_K", "coflow_mask", "port_mask",
    )
    ins = [arrays[k] for k in names]
    B, Mp = ins[0].shape[:2]
    if B == 0 or Mp == 0:
        # Degenerate bucket (empty ensemble, or every member has M=0):
        # nothing to iterate on — the solution is identically zero.
        return LPSolutionBatch(
            completion=np.zeros((B, Mp)),
            y=np.zeros((B, Mp, Mp)),
            objective=np.zeros(B),
            method="subgradient_batch",
            iterations=iters,
        )
    from repro.launch.mesh import data_axis_size, place

    # XLA drops a batch dimension of one, and the plain matmul it lowers
    # to accumulates in another order than the batched contraction: a
    # member's trajectory would then depend on how many members share its
    # device.  Fully-masked members keep every device's block at two or
    # more, so results are bit-identical across batch sizes and meshes.
    n_dev = 1 if sharding is None else data_axis_size(sharding.mesh)
    Bp = padded_members(B, n_dev)
    if Bp > B:
        ins = [_pad_members(x, Bp) for x in ins]
    ins = [place(x, sharding) for x in ins]
    best_Y, T_best, best_F, _ = _subgradient_run_batch(*ins, iters=iters)
    # Device-resident (and, under ``sharding``, device-sharded) result;
    # `unpack` / `experiments.results.device_gather` bring it to host.
    return LPSolutionBatch(
        completion=T_best[:B],
        y=best_Y[:B],
        objective=best_F[:B],
        method="subgradient_batch",
        iterations=iters,
    )


def solve_subgradient_batch(
    instances: Sequence[CoflowInstance],
    iters: int = 3000,
    warm_start_orders: Sequence[np.ndarray | None] | None = None,
    pad_coflows: int | None = None,
    pad_ports: int | None = None,
    sharding=None,
) -> list[LPSolution]:
    """Solve the ordering LP for a whole ensemble in one vectorized program.

    Instances are zero-padded to a shared bucket shape (``pad_coflows``
    coflows x ``pad_ports`` flat ports, defaulting to the ensemble maxima)
    and the projected-subgradient iteration runs batched over the leading
    ensemble axis — the per-step (B, Mp, Mp) @ (B, Mp, Pp) contractions are
    exactly the `lp_terms_batch` kernel's shape.  Padded coflows and ports
    are masked out of the max terms and carry zero weight, so each member's
    trajectory matches what `solve_subgradient` computes for it alone (up
    to f32 reduction-order noise).

    This is the list-in/list-out convenience wrapper over the array
    pipeline (`pack_lp_arrays` -> `solve_subgradient_batch_arrays` ->
    `LPSolutionBatch.unpack`); batch-first callers keep the padded
    `LPSolutionBatch` instead.  Returns one `LPSolution` per instance, in
    input order.
    """
    instances = list(instances)
    if not instances:
        return []
    arrays = pack_lp_arrays(
        instances, pad_coflows, pad_ports, warm_start_orders
    )
    batch = solve_subgradient_batch_arrays(
        arrays, iters=iters, sharding=sharding
    )
    return batch.unpack([inst.num_coflows for inst in instances])


# ---------------------------------------------------------------------------
# Device-resident warm state (streaming epochs)
# ---------------------------------------------------------------------------
#
# The streaming service keeps one (S, S) precedence matrix and a (S,) solved
# mask on device for the life of a stream; each epoch gathers the active
# slots' pairwise precedences into the dense warm start and scatters the
# solved pairs back — both as fixed-shape jits (slot vectors padded to S with
# the out-of-range index S), so the warm state never round-trips through the
# host and the epoch step stays compile-stable across varying active counts.


@jax.jit
def warm_gather_device(Yw, solved, slots, default_Y0):
    """Warm-start gather: overwrite solved pairs of the dense Y0.

    ``Yw`` (S, S) f32 and ``solved`` (S,) bool are the resident warm
    state; ``slots`` (S,) i32 maps dense position d -> slot id (padded
    positions hold S, gathered as unsolved/zero); ``default_Y0`` (S, S)
    f32 is the epoch's cold warm start.  Returns ``(Y0, any_warm)``:
    strict-upper Y0 with previously-solved pairs replaced by their last
    precedence, and whether any pair was warm (a scalar the host reads
    to pick the reduced warm iteration budget).
    """
    prev = jnp.take(solved, slots, mode="fill", fill_value=False)
    both = prev[:, None] & prev[None, :]
    rows = jnp.take(Yw, slots, axis=0, mode="fill", fill_value=0.0)
    Ys = jnp.take(rows, slots, axis=1, mode="fill", fill_value=0.0)
    upper = jnp.triu(jnp.ones(Yw.shape, dtype=bool), k=1)
    warm_pair = both & upper
    Y0 = jnp.where(warm_pair, Ys, default_Y0)
    return jnp.triu(Y0, k=1), warm_pair.any()


@jax.jit
def warm_scatter_device(Yw, slots, y):
    """Scatter an epoch's solved precedences back into the warm state.

    ``y`` (S, S) f32 is the batched solver's strict-upper solution for
    the dense epoch (row/col d = dense position d).  The full precedence
    matrix (x_ab + x_ba = 1, zero diagonal) is formed on device and
    written at ``(slots[a], slots[b])``; padded positions carry slot
    index S and are dropped by the scatter.  Returns the updated ``Yw``
    (the small (S,) solved mask is host-side bookkeeping — the gather
    masks by it, so stale rows never need clearing).
    """
    u = jnp.triu(y, k=1)
    full = u + jnp.tril(1.0 - u.T, k=-1)
    return Yw.at[slots[:, None], slots[None, :]].set(full, mode="drop")

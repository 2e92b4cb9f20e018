"""Pallas TPU kernel: fused ordering-LP term evaluation.

Computes  max_p (X^T P_rho)[m, p] * inv_R  and  max_p (X^T P_tau)[m, p] *
delta_over_K  in one pass.  This is the per-iteration oracle of the JAX LP
solver (core/lp.py) — two (M, M) @ (M, 2N) matmuls feeding a row-max.  On
TPU the matmuls hit the MXU with (bm, bk) x (bk, P) tiles; the row-max and
scaling fuse into the epilogue so the (M, 2N) products never round-trip to
HBM.

Tiling: grid (m_tiles, k_tiles), k innermost (arbitrary->reduction order);
the full padded port width P (2N rounded to a lane multiple) rides along in
VMEM — port counts are small (2N <= few hundred) so a (bk, P) block is a few
hundred KB.  Two f32 VMEM scratch accumulators of shape (bm, P) hold the
partial products; on the final k step the scaled row-max lands in a
(bm, LANE) output tile (lane-broadcast, column 0 is read back).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (
    LANE,
    pad_to,
    round_up,
    use_interpret,
)


def _lp_terms_kernel(
    x_ref, rho_ref, tau_ref, load_ref, rec_ref, acc_rho, acc_tau,
    *, k_tiles: int, inv_R: float, delta_over_K: float,
):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_rho[...] = jnp.zeros_like(acc_rho)
        acc_tau[...] = jnp.zeros_like(acc_tau)

    x_blk = x_ref[...]  # (bk, bm) — X[q_tile, m_tile]
    xt = x_blk.T  # (bm, bk)
    acc_rho[...] += jnp.dot(
        xt, rho_ref[...], preferred_element_type=jnp.float32
    )
    acc_tau[...] += jnp.dot(
        xt, tau_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == k_tiles - 1)
    def _epilogue():
        t_load = jnp.max(acc_rho[...], axis=1) * inv_R  # (bm,)
        t_rec = jnp.max(acc_tau[...], axis=1) * delta_over_K
        load_ref[...] = jnp.broadcast_to(t_load[:, None], load_ref.shape)
        rec_ref[...] = jnp.broadcast_to(t_rec[:, None], rec_ref.shape)


def _lp_terms_batch_kernel(
    invr_ref, dok_ref, x_ref, rho_ref, tau_ref, load_ref, rec_ref,
    acc_rho, acc_tau, *, k_tiles: int,
):
    b = pl.program_id(0)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_rho[...] = jnp.zeros_like(acc_rho)
        acc_tau[...] = jnp.zeros_like(acc_tau)

    x_blk = x_ref[0]  # (bk, bm) — X[b, q_tile, m_tile]
    xt = x_blk.T  # (bm, bk)
    acc_rho[...] += jnp.dot(
        xt, rho_ref[0], preferred_element_type=jnp.float32
    )
    acc_tau[...] += jnp.dot(
        xt, tau_ref[0], preferred_element_type=jnp.float32
    )

    @pl.when(k == k_tiles - 1)
    def _epilogue():
        # Per-instance scales live in SMEM via scalar prefetch; indexing by
        # the batch grid coordinate keeps the scaling fused in the epilogue.
        inv_R = invr_ref[b]
        dok = dok_ref[b]
        t_load = jnp.max(acc_rho[...], axis=1) * inv_R  # (bm,)
        t_rec = jnp.max(acc_tau[...], axis=1) * dok
        load_ref[0] = jnp.broadcast_to(t_load[:, None], load_ref.shape[1:])
        rec_ref[0] = jnp.broadcast_to(t_rec[:, None], rec_ref.shape[1:])


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_k", "interpret")
)
def lp_terms_batch_pallas(
    x: jnp.ndarray,
    p_rho: jnp.ndarray,
    p_tau: jnp.ndarray,
    inv_R: jnp.ndarray,
    delta_over_K: jnp.ndarray,
    block_m: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Batched fused LP-term evaluation — one launch for a whole ensemble.

    x: (B, M, M) diag=1; p_rho/p_tau: (B, M, P); inv_R/delta_over_K: (B,)
    per-instance scales (instances in an ensemble have their own R, delta,
    K).  Returns (t_load, t_rec), each (B, M).

    Grid (B, m_tiles, k_tiles): the leading batch dimension is parallel, so
    the two (B, M, M) @ (B, M, 2N) contractions of the whole ensemble run as
    a single kernel launch instead of B Python-looped calls — at the small
    M of a single instance the MXU is otherwise starved.
    """
    if interpret is None:
        interpret = use_interpret()
    B, M = x.shape[0], x.shape[1]
    P = p_rho.shape[2]
    Mp = round_up(M, max(block_m, block_k))
    Pp = round_up(P, LANE)
    xf = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, Mp - M), (0, Mp - M)))
    rho = jnp.pad(
        p_rho.astype(jnp.float32), ((0, 0), (0, Mp - M), (0, Pp - P))
    )
    tau = jnp.pad(
        p_tau.astype(jnp.float32), ((0, 0), (0, Mp - M), (0, Pp - P))
    )

    m_tiles = Mp // block_m
    k_tiles = Mp // block_k
    grid = (B, m_tiles, k_tiles)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            # Index maps receive the scalar-prefetch refs as trailing args.
            pl.BlockSpec((1, block_k, block_m), lambda b, m, k, *_: (b, k, m)),
            pl.BlockSpec((1, block_k, Pp), lambda b, m, k, *_: (b, k, 0)),
            pl.BlockSpec((1, block_k, Pp), lambda b, m, k, *_: (b, k, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_m, LANE), lambda b, m, k, *_: (b, m, 0)),
            pl.BlockSpec((1, block_m, LANE), lambda b, m, k, *_: (b, m, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_m, Pp), jnp.float32),
            pltpu.VMEM((block_m, Pp), jnp.float32),
        ],
    )
    load, rec = pl.pallas_call(
        functools.partial(_lp_terms_batch_kernel, k_tiles=k_tiles),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Mp, LANE), jnp.float32),
            jax.ShapeDtypeStruct((B, Mp, LANE), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="lp_terms_batch",
    )(
        jnp.asarray(inv_R, jnp.float32),
        jnp.asarray(delta_over_K, jnp.float32),
        xf,
        rho,
        tau,
    )
    return load[:, :M, 0], rec[:, :M, 0]


@functools.partial(
    jax.jit,
    static_argnames=("inv_R", "delta_over_K", "block_m", "block_k", "interpret"),
)
def lp_terms_pallas(
    x: jnp.ndarray,
    p_rho: jnp.ndarray,
    p_tau: jnp.ndarray,
    inv_R: float,
    delta_over_K: float,
    block_m: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x: (M, M) diag=1; p_rho/p_tau: (M, P).  Returns (t_load, t_rec) (M,)."""
    if interpret is None:
        interpret = use_interpret()
    M = x.shape[0]
    P = p_rho.shape[1]
    Mp = round_up(M, max(block_m, block_k))
    Pp = round_up(P, LANE)
    xf = jnp.pad(
        x.astype(jnp.float32), ((0, Mp - M), (0, Mp - M))
    )
    rho = jnp.pad(p_rho.astype(jnp.float32), ((0, Mp - M), (0, Pp - P)))
    tau = jnp.pad(p_tau.astype(jnp.float32), ((0, Mp - M), (0, Pp - P)))

    m_tiles = Mp // block_m
    k_tiles = Mp // block_k
    grid = (m_tiles, k_tiles)
    load, rec = pl.pallas_call(
        functools.partial(
            _lp_terms_kernel,
            k_tiles=k_tiles,
            inv_R=inv_R,
            delta_over_K=delta_over_K,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_k, block_m), lambda m, k: (k, m)),  # X[q, m]
            pl.BlockSpec((block_k, Pp), lambda m, k: (k, 0)),
            pl.BlockSpec((block_k, Pp), lambda m, k: (k, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_m, LANE), lambda m, k: (m, 0)),
            pl.BlockSpec((block_m, LANE), lambda m, k: (m, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Mp, LANE), jnp.float32),
            jax.ShapeDtypeStruct((Mp, LANE), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_m, Pp), jnp.float32),
            pltpu.VMEM((block_m, Pp), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="lp_terms",
    )(xf, rho, tau)
    return load[:M, 0], rec[:M, 0]

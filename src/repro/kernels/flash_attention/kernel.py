"""Pallas TPU kernel: GQA flash attention (fwd) with causal/sliding window.

IO-aware attention in the FlashAttention style, adapted to the TPU memory
hierarchy: (bq, D) query tiles stay resident in VMEM while (bk, D) key/value
tiles stream through; the (bq, bk) logit tile lives only in VREGs/VMEM and
the online-softmax statistics (running max m, denominator l) are carried in
VMEM scratch across the innermost key-tile grid axis.  GQA is expressed in
the kv index_map (query head h reads kv head h // group) so no repeated KV
is ever materialized.  Tiles entirely outside the causal/sliding-window band
are skipped with pl.when — for gemma3-style local attention (window 1024 of
a 32k sequence) that removes ~97% of the tiles.

Numerics: running max initialized to -1e30 (finite) so fully-masked rows
flow through as zeros without NaN special-casing; accumulation in f32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import LANE, round_up, use_interpret

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, scale: float, causal: bool, window: int | None,
    block_q: int, block_k: int, k_tiles: int, kv_len: int, q_offset: int,
):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Tile-level band check: is any (q, k) pair in this tile unmasked?
    q_lo = i * block_q + q_offset
    q_hi = q_lo + block_q - 1
    k_lo = j * block_k
    k_hi = k_lo + block_k - 1
    live = k_lo < kv_len  # padding tiles are dead
    if causal:
        live &= k_lo <= q_hi
    if window is not None:
        live &= k_hi > q_lo - window

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (bq, bk)
        qi = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kj = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = kj < kv_len
        if causal:
            mask &= qi >= kj
        if window is not None:
            mask &= (qi - kj) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0]  # (bq,)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)  # finite: both >= NEG_INF
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        l_new = l_ref[:, 0] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(j == k_tiles - 1)
    def _epilogue():
        l = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "q_offset", "block_q", "block_k", "interpret"
    ),
)
def flash_attention_pallas(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    window: int | None = None,
    q_offset: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """q: (B, Hq, Sq, D); k/v: (B, Hkv, Skv, D) -> (B, Hq, Sq, D)."""
    if interpret is None:
        interpret = use_interpret()
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    if Hq % Hkv:
        raise ValueError(f"Hq={Hq} not a multiple of Hkv={Hkv}")
    group = Hq // Hkv
    scale = 1.0 / (D ** 0.5)

    bq = min(block_q, max(8, round_up(Sq, 8)))
    bk = min(block_k, max(128, round_up(Skv, 128)))
    Sqp = round_up(Sq, bq)
    Skvp = round_up(Skv, bk)
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, Sqp - Sq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, Skvp - Skv), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, Skvp - Skv), (0, 0)))

    k_tiles = Skvp // bk
    grid = (B, Hq, Sqp // bq, k_tiles)
    out = pl.pallas_call(
        functools.partial(
            _flash_kernel,
            scale=scale,
            causal=causal,
            window=window,
            block_q=bq,
            block_k=bk,
            k_tiles=k_tiles,
            kv_len=Skv,
            q_offset=q_offset,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec(
                (1, 1, bk, D), lambda b, h, i, j, g=group: (b, h // g, j, 0)
            ),
            pl.BlockSpec(
                (1, 1, bk, D), lambda b, h, i, j, g=group: (b, h // g, j, 0)
            ),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sqp, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, LANE), jnp.float32),
            pltpu.VMEM((bq, LANE), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention_gqa",
    )(qp, kp, vp)
    return out[:, :, :Sq, :]

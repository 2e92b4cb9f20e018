"""Pallas TPU kernel: fused chunkwise mLSTM with VMEM-resident state.

The xlstm hillclimb (EXPERIMENTS.md §Perf Cell A) showed the chunk-scan's
HBM traffic is dominated by the (Dh x Dh) matrix state and per-chunk
intermediates round-tripping per chunk.  This kernel keeps the running
state S (Dh x Dh, f32 — 1 MB for Dh=512) and normalizer n in VMEM scratch
across the sequential chunk grid axis, so per chunk only the (C, Dh)
q/k/v tiles and the (C, Dh) output tile move through HBM — the TPU-native
realization of the chunkwise-parallel mLSTM.

Grid: (BH, n_chunks) with the chunk axis sequential ("arbitrary").  Per
chunk (all in f32 on the MXU):

    F      = cumsum(log_f)                         (C,)
    inter  = (q * e^F) @ S_prev                    (C, Dh)
    A[t,s] = e^{F_t - F_s + log_i_s} * [s <= t]    (C, C)
    scores = (q k^T) * A                           (C, C)
    h      = (inter + scores @ v) / max(|den|, 1)
    S     += outer(k * w, v),  w = e^{F_C - F + log_i}
    n     += (k * w) summed over the chunk
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import use_interpret


def _mlstm_kernel(
    q_ref, k_ref, v_ref, lf_ref, li_ref, h_ref, s_out, n_out,
    s_ref, n_ref, *, n_chunks: int,
):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)
        n_ref[...] = jnp.zeros_like(n_ref)

    q = q_ref[0, 0].astype(jnp.float32)  # (C, Dh)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    lf = lf_ref[0, 0].astype(jnp.float32)  # (C, 1)
    li = li_ref[0, 0].astype(jnp.float32)

    F = jnp.cumsum(lf, axis=0)  # (C, 1) inclusive cumulative log-forget
    F_total = F[-1:, :]  # (1, 1)

    q_dec = q * jnp.exp(F)  # (C, Dh)
    inter = jnp.dot(q_dec, s_ref[...], preferred_element_type=jnp.float32)
    inter_n = jnp.dot(
        q_dec, n_ref[...].T, preferred_element_type=jnp.float32
    )  # (C, 1)

    # Intra-chunk decay matrix A[t, s] = exp(F_t - F_s + li_s) for s <= t.
    gate = F - F.T + li.T  # (C, C)
    C = q.shape[0]
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    A = jnp.where(t_idx >= s_idx, jnp.exp(gate), 0.0)
    scores = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * A
    intra = jnp.dot(scores, v, preferred_element_type=jnp.float32)

    num = inter + intra
    den = inter_n + jnp.sum(scores, axis=1, keepdims=True)  # (C, 1)
    h = num / jnp.maximum(jnp.abs(den), 1.0)
    h_ref[0, 0] = h.astype(h_ref.dtype)

    # State update.
    w = jnp.exp(F_total - F + li)  # (C, 1)
    kw = k * w
    s_ref[...] = s_ref[...] * jnp.exp(F_total) + jnp.dot(
        kw.T, v, preferred_element_type=jnp.float32
    )
    n_ref[...] = n_ref[...] * jnp.exp(F_total) + jnp.sum(
        kw, axis=0, keepdims=True
    )

    @pl.when(c == n_chunks - 1)
    def _emit_state():
        s_out[0] = s_ref[...]
        n_out[0] = n_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mlstm_chunk_pallas(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    log_f: jnp.ndarray,
    log_i: jnp.ndarray,
    chunk: int = 256,
    interpret: bool | None = None,
):
    """q/k/v: (BH, S, Dh); log_f/log_i: (BH, S).

    Returns (h (BH, S, Dh) in q.dtype, (S_state (BH, Dh, Dh) f32,
    n (BH, Dh) f32)).  S must be a multiple of `chunk` (pad upstream).
    """
    if interpret is None:
        interpret = use_interpret()
    BH, S, Dh = q.shape
    C = min(chunk, S)
    if S % C:
        raise ValueError(f"S={S} not a multiple of chunk={C}")
    NC = S // C
    qc = q.reshape(BH, NC, C, Dh)
    kc = k.reshape(BH, NC, C, Dh)
    vc = v.reshape(BH, NC, C, Dh)
    lfc = log_f.reshape(BH, NC, C, 1)
    lic = log_i.reshape(BH, NC, C, 1)

    h, s_fin, n_fin = pl.pallas_call(
        functools.partial(_mlstm_kernel, n_chunks=NC),
        grid=(BH, NC),
        in_specs=[
            pl.BlockSpec((1, 1, C, Dh), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, C, Dh), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, C, Dh), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, C, 1), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, C, 1), lambda b, c: (b, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, C, Dh), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, Dh, Dh), lambda b, c: (b, 0, 0)),
            pl.BlockSpec((1, 1, Dh), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, NC, C, Dh), q.dtype),
            jax.ShapeDtypeStruct((BH, Dh, Dh), jnp.float32),
            jax.ShapeDtypeStruct((BH, 1, Dh), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((Dh, Dh), jnp.float32),
            pltpu.VMEM((1, Dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="mlstm_chunk",
    )(qc, kc, vc, lfc, lic)
    return h.reshape(BH, S, Dh), (s_fin, n_fin[:, 0])
